"""Plain reference for the §8 microbenchmark, independent of the program.

The raw table as numpy columns. Writes are applied in commit order, the
last writer of a cell winning; each query is answered by a full scan of
the raw values. Per-column value counts (dense over the value domain) are
kept up to date write by write, so a self-join costs one gather instead of
a sort. Nothing here imports the program or reads anything it made.
"""

from __future__ import annotations

import numpy as np

from chipbench.gen import OP_MODIFY, Query, Txns


class Reference:
    """The table at one visibility point, advanced by ``apply``."""

    def __init__(self, table: np.ndarray, domain: int):
        self.cols = [np.array(table[:, j]) for j in range(table.shape[1])]
        self.domain = domain
        self._counts: dict[int, np.ndarray] = {}

    def apply(self, txns: Txns) -> None:
        """Apply a commit-ordered slice: each modified cell takes the value
        of its last write in the slice."""
        writes = txns.op == OP_MODIFY
        for c, col in enumerate(self.cols):
            m = writes & (txns.col == c)
            if not m.any():
                continue
            rows, vals = txns.row[m][::-1], txns.value[m][::-1]
            rows, last = np.unique(rows, return_index=True)
            vals = vals[last]
            if c in self._counts:
                np.subtract.at(self._counts[c], col[rows], 1)
                np.add.at(self._counts[c], vals, 1)
            col[rows] = vals

    def value_counts(self, c: int) -> np.ndarray:
        if c not in self._counts:
            self._counts[c] = np.bincount(self.cols[c],
                                          minlength=self.domain)
        return self._counts[c]

    def answer(self, q: Query) -> int:
        """SUM of the aggregate column over rows whose filter value lies in
        [lo, hi], plus, for a join, the number of (selected row, any row)
        pairs with equal join-column values."""
        f = self.cols[q.filter_col]
        sel = (f >= q.lo) & (f <= q.hi)
        total = self.cols[q.agg_col][sel].astype(np.int64).sum()
        if q.join_col is not None:
            counts = self.value_counts(q.join_col)
            total = total + counts[self.cols[q.join_col][sel]].sum(
                dtype=np.int64)
        return int(total)
