"""Seeded data and traffic for the benchmark: the paper's §8 microbenchmark.

Copies of the program's generators (``schema.gen_table``,
``schema.gen_update_stream``, ``engine.gen_queries`` and the §8 mix of
``benchmarks/common.workload``), kept here so that no later change to the
program can move the yardstick. They keep the originals' distributions;
where a draw was a Bernoulli share or a Poisson count, it is a fixed count
in a seeded order instead, so that every seed carries the same amount of
work (the same number of writes and joins) in another order. The query
arrival times are the same for every seed (``arrival_times``).

Everything is plain numpy. The harness turns the arrays into the program's
own types; the reference reads them as they are.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Transaction ops, as the program's update stream encodes them.
OP_READ, OP_MODIFY = 0, 1
# Transactions generated per block of the lazily grown stream.
STREAM_BLOCK = 1 << 16


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent generator per purpose, all derived from ``--seed``."""
    return np.random.default_rng([int(seed), *stream])


def gen_table(seed: int, n_rows: int, n_cols: int, distinct: int,
              domain: int) -> np.ndarray:
    """(n_rows, n_cols) int32: column j draws from a pool of ``distinct``
    values spread over ``[0, domain)``, as ``schema.gen_table`` does."""
    rng = rng_for(seed, 0)
    table = np.empty((n_rows, n_cols), dtype=np.int32)
    for j in range(n_cols):
        pool = rng.choice(domain, size=distinct, replace=False).astype(
            np.int32)
        table[:, j] = pool[rng.integers(0, distinct, size=n_rows,
                                        dtype=np.uint16)]
    return table


@dataclasses.dataclass
class Txns:
    """A contiguous slice of the transaction stream (commit order)."""

    thread_id: np.ndarray  # (n,) int32
    commit_id: np.ndarray  # (n,) int64, global total order
    op: np.ndarray         # (n,) int8: OP_READ or OP_MODIFY
    row: np.ndarray        # (n,) int64
    col: np.ndarray        # (n,) int32
    value: np.ndarray      # (n,) int32

    def __len__(self) -> int:
        return int(self.commit_id.shape[0])

    def slice(self, lo: int, hi: int) -> "Txns":
        return Txns(*(getattr(self, f.name)[lo:hi]
                      for f in dataclasses.fields(self)))

    @staticmethod
    def concat(parts: list["Txns"]) -> "Txns":
        return Txns(*(np.concatenate([getattr(p, f.name) for p in parts])
                      for f in dataclasses.fields(Txns)))


class TxnStream:
    """The §8 transactional microbenchmark as an endless seeded stream:
    single-cell reads or modifies of uniform rows and columns, spread over
    ``threads`` threads, new values uniform over the value domain
    (``schema.gen_update_stream`` with ``zipf_skew=0``). Each block of
    ``STREAM_BLOCK`` transactions holds exactly ``write_share`` of
    modifies, in a seeded order."""

    def __init__(self, seed: int, n_rows: int, n_cols: int, domain: int,
                 write_share: float, threads: int):
        self.seed, self.n_rows, self.n_cols = seed, n_rows, n_cols
        self.domain, self.write_share, self.threads = (domain, write_share,
                                                       threads)
        self._blocks: list[Txns] = []
        self._next = 0

    def _block(self, b: int) -> Txns:
        rng = rng_for(self.seed, 1, b)
        n = STREAM_BLOCK
        n_writes = int(round(self.write_share * n))
        op = np.zeros(n, dtype=np.int8)
        op[rng.permutation(n)[:n_writes]] = OP_MODIFY
        return Txns(
            thread_id=rng.integers(0, self.threads, size=n).astype(np.int32),
            commit_id=np.arange(b * n, (b + 1) * n, dtype=np.int64),
            op=op,
            row=rng.integers(0, self.n_rows, size=n).astype(np.int64),
            col=rng.integers(0, self.n_cols, size=n).astype(np.int32),
            value=rng.integers(0, self.domain, size=n).astype(np.int32))

    def take(self, n: int) -> Txns:
        """The next ``n`` transactions of the stream."""
        lo, hi = self._next, self._next + n
        while len(self._blocks) * STREAM_BLOCK < hi:
            self._blocks.append(self._block(len(self._blocks)))
        b0, b1 = lo // STREAM_BLOCK, (hi - 1) // STREAM_BLOCK
        part = Txns.concat(self._blocks[b0:b1 + 1])
        off = b0 * STREAM_BLOCK
        self._next = hi
        return part.slice(lo - off, hi - off)

    def take_writes(self, counts) -> Txns:
        """The next ``sum(counts)`` transactions of the stream, each made a
        modify: the first ``counts[0]`` of column 0, the next ``counts[1]``
        of column 1, and so on. Rows and values stay the stream's."""
        t = self.take(int(sum(counts)))
        t.op = np.full(len(t), OP_MODIFY, dtype=np.int8)
        t.col = np.repeat(np.arange(len(counts), dtype=np.int32), counts)
        return t

    @property
    def taken(self) -> int:
        return self._next


@dataclasses.dataclass(frozen=True)
class Query:
    """SELECT SUM(agg) FROM t WHERE lo <= filter <= hi, plus, with a join
    column, the count of value-equal pairs (selected row, any row) on it."""

    filter_col: int
    lo: int
    hi: int
    agg_col: int
    join_col: int | None


def gen_queries(rng: np.random.Generator, n: int, n_cols: int, domain: int,
                selectivity: float, join_share: float) -> list[Query]:
    """``engine.gen_queries``: uniform filter and aggregate columns, a value
    range of ``selectivity`` of the domain, and a self-join on a uniform
    column for exactly ``round(join_share * n)`` of the queries."""
    n_join = int(round(join_share * n))
    joins = np.zeros(n, dtype=bool)
    joins[rng.permutation(n)[:n_join]] = True
    width = int(domain * selectivity)
    f = rng.integers(0, n_cols, size=n)
    a = rng.integers(0, n_cols, size=n)
    lo = rng.integers(0, int(domain * (1 - selectivity)), size=n)
    j = rng.integers(0, n_cols, size=n)
    return [Query(int(f[i]), int(lo[i]), int(lo[i]) + width, int(a[i]),
                  int(j[i]) if joins[i] else None) for i in range(n)]


# The arrival trace is one for every run: what arrives (the queries, the
# data, the transactions) comes from --seed, when it arrives does not. Query
# batches form from the arrival times, and on one host thread how they form
# sets how much time the transactions get, so a seeded order of the gaps
# would make the load differ from seed to seed.
ARRIVALS = 4


def arrival_times(rate: float, seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of an open-loop Poisson process at
    ``rate`` per second: ``round(rate * seconds)`` arrivals whose gaps are
    the exponential distribution's quantiles in one fixed order, scaled to
    fill the window."""
    n = int(round(rate * seconds))
    if n == 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng_for(ARRIVALS).permutation(gaps) * (seconds / gaps.sum())
    return np.cumsum(gaps) - gaps
