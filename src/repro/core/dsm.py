"""DSM (column-store) replica with order-preserving dictionary encoding (§5.2, §7.1).

Each column is stored as fixed-width integer codes plus a sorted dictionary
(real value -> code is order-preserving: code order == value order). Range
predicates on values therefore become range predicates on codes without
decoding — the optimization that makes DSM scans fast and update application
hard, which is exactly the tension the paper's update-application unit
resolves.

All functions are pure and jit-compatible (jnp); `encode_column` is the only
one that inspects data-dependent shapes (dictionary size) and therefore runs
outside jit (like a real system: encoding happens at update-application
time, on the accelerator, with a bounded 1024-entry update dictionary).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schema import VALUE_BYTES


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EncodedColumn:
    """Dictionary-encoded column.

    codes:      (n,) int32 — index into `dictionary`
    dictionary: (k,) int32 — sorted distinct values (order-preserving)
    valid:      (n,) bool  — row validity (deletes mark rows invalid)
    version:    int        — bumped by every update application (Phase-2 swap)
    """

    codes: jnp.ndarray
    dictionary: jnp.ndarray
    valid: jnp.ndarray
    version: int = 0

    # -- pytree plumbing --------------------------------------------------
    def tree_flatten(self):
        return (self.codes, self.dictionary, self.valid), (self.version,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, dictionary, valid = children
        return cls(codes=codes, dictionary=dictionary, valid=valid, version=aux[0])

    # -- properties priced by the cost model ------------------------------
    @property
    def n_rows(self) -> int:
        return int(self.codes.shape[0])

    @property
    def dict_size(self) -> int:
        return int(self.dictionary.shape[0])

    @property
    def bit_width(self) -> int:
        """Fixed-length code width the paper's compression would use."""
        return max(1, math.ceil(math.log2(max(self.dict_size, 2))))

    @property
    def encoded_bytes(self) -> float:
        return self.n_rows * self.bit_width / 8.0

    @property
    def raw_bytes(self) -> float:
        return self.n_rows * VALUE_BYTES


def encode_column(values: np.ndarray) -> EncodedColumn:
    """Build the sorted dictionary and encode (order-preserving)."""
    values = np.asarray(values)
    dictionary, codes = np.unique(values, return_inverse=True)
    # columns are host numpy; the jitted kernels convert at dispatch
    return EncodedColumn(
        codes=codes.astype(np.int32),
        dictionary=dictionary.astype(np.int32),
        valid=np.ones(values.shape[0], dtype=bool),
        version=0,
    )


def decode_column(col: EncodedColumn) -> jnp.ndarray:
    """Decode codes back to real values (gather through the dictionary)."""
    return col.dictionary[col.codes]


def value_range_to_code_range(col: EncodedColumn, lo: int, hi: int):
    """Map a value-range predicate to a code-range predicate (no decode).

    Returns (code_lo, code_hi) such that  lo <= value <= hi  <=>
    code_lo <= code < code_hi. This is the order-preserving-dictionary
    fast path used by the analytical engine's scans.
    """
    dictionary = np.asarray(col.dictionary)
    code_lo = int(np.searchsorted(dictionary, lo, side="left"))
    code_hi = int(np.searchsorted(dictionary, hi, side="right"))
    return code_lo, code_hi


def new_values(old_dict: np.ndarray, update_dict: np.ndarray
               ) -> tuple[np.ndarray, np.ndarray]:
    """``(thresholds, values)``: the values of the sorted update
    dictionary that the old one lacks, and where each lands among the old
    codes, ``searchsorted(old_dict, u)``, both ascending. Every old value
    survives a merge, so the merged dictionary is ``old_dict`` with the
    new values inserted at their thresholds, and an old code ``c`` moves
    to ``c + #{t : t <= c}``."""
    pos = np.searchsorted(old_dict, update_dict)
    known = np.zeros(len(pos), dtype=bool)
    inside = pos < len(old_dict)
    known[inside] = old_dict[pos[inside]] == update_dict[inside]
    return pos[~known], update_dict[~known]


# ---------------------------------------------------------------------------
# Delta store: sorted per-column overlay of not-yet-compacted updates
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ColumnDelta:
    """Sorted row-keyed overlay of updates not yet folded into the base.

    The delta-store update plane appends shipped updates here instead of
    rebuilding the column (no dictionary merge, no full re-encode); scans
    merge base + overlay on the fly and a background compaction folds the
    overlay into the base column once `n_entries` crosses the capacity
    threshold. One entry per touched row (last-writer-wins within and
    across batches):

    rows:      (d,) int64 sorted unique row ids, all < n_base
    values:    (d,) int32 the row's current raw value — the last written
               value, or the base value carried over for delete-only rows
               (deletes keep the row's value, matching the eager path's
               code retention; aggregates still read it when f-selected)
    valid:     (d,) bool  row validity after the overlayed ops
    cids:      (d,) int64 latest commit id touching the row (compaction
               replays entries in this order)
    n_base:    base-column row count the overlay is relative to
    n_entries: RAW appended entry count since the last compaction — the
               capacity trigger (overlay rows dedupe, work done doesn't)
    """

    rows: np.ndarray
    values: np.ndarray
    valid: np.ndarray
    cids: np.ndarray
    n_base: int
    n_entries: int = 0

    @property
    def n_overlay(self) -> int:
        return int(self.rows.shape[0])


def empty_delta(col: EncodedColumn) -> ColumnDelta:
    """Fresh (empty) overlay relative to `col`'s current row count."""
    return ColumnDelta(rows=np.empty(0, dtype=np.int64),
                       values=np.empty(0, dtype=np.int32),
                       valid=np.empty(0, dtype=bool),
                       cids=np.empty(0, dtype=np.int64),
                       n_base=col.n_rows, n_entries=0)


# ---------------------------------------------------------------------------
# Row-wise sharding (§4's multiple analytical islands, one DSM shard each)
# ---------------------------------------------------------------------------

def shard_bounds(n_rows: int, n_shards: int) -> list[int]:
    """Contiguous row partition boundaries: shard s owns [b[s], b[s+1]).

    The split produces at most two distinct shard sizes, so per-shard kernel
    calls reuse at most two compiled shapes (the property that makes the
    fan-out `jax.vmap`-able when sizes coincide).
    """
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    return [n_rows * s // n_shards for s in range(n_shards + 1)]


def shard_column(col: EncodedColumn, n_shards: int) -> list[EncodedColumn]:
    """Partition a column row-wise into `n_shards` island-local shards.

    Dictionary encoding is preserved: every shard shares the (replicated)
    dictionary object, so codes remain comparable across shards and
    `concat_columns` is an exact inverse. `valid` masks are sliced with the
    rows; a shard may be empty when n_shards > n_rows.
    """
    bounds = shard_bounds(col.n_rows, n_shards)
    return [
        EncodedColumn(codes=col.codes[lo:hi], dictionary=col.dictionary,
                      valid=col.valid[lo:hi], version=col.version)
        for lo, hi in zip(bounds, bounds[1:])
    ]


def concat_columns(shards: list[EncodedColumn]) -> EncodedColumn:
    """Reassemble shard columns (inverse of `shard_column`).

    All shards must carry the same dictionary and version — mixing shards
    from different update rounds would silently decode rows through the
    wrong dictionary, so that is rejected here rather than at query time.
    """
    if not shards:
        raise ValueError("concat_columns needs at least one shard")
    head = shards[0]
    for s in shards[1:]:
        if s.version != head.version:
            raise ValueError(
                f"shard version mismatch: {s.version} != {head.version}")
        if s.dictionary is not head.dictionary and not (
                s.dictionary.shape == head.dictionary.shape
                and bool(jnp.array_equal(s.dictionary, head.dictionary))):
            raise ValueError("shard dictionary mismatch (different rounds?)")
    if len(shards) == 1:
        return EncodedColumn(codes=head.codes, dictionary=head.dictionary,
                             valid=head.valid, version=head.version)
    return EncodedColumn(
        codes=jnp.concatenate([s.codes for s in shards]),
        dictionary=head.dictionary,
        valid=jnp.concatenate([s.valid for s in shards]),
        version=head.version,
    )


class StaleShardedViewError(RuntimeError):
    """A ShardedView was used after its source column was swapped out.

    The sharded snapshot plane materializes each pinned column's shards
    once per query round; a Phase-2 pointer swap (or snapshot-chain GC)
    invalidates any unpinned view built from the superseded column.
    Staleness is a *hard error* — never a silently-refreshed cache — so a
    scan can never mix rounds without the caller noticing.
    """


@dataclasses.dataclass
class ShardedView:
    """Materialized island-resident shards of one pinned column.

    The paper's analytical islands each *own* a resident DSM shard (§4,
    Fig. 5). This is that residency made explicit: the column's rows are
    partitioned by `shard_bounds` and stacked into equal-shaped
    ``(n_shards, width)`` arrays — `shard_bounds` produces at most two
    shard sizes differing by one row, so every shard except the smaller
    "tail" shards carries zero padding, and padded slots are marked
    ``valid=False`` (they contribute the exact identity to every scan).
    The stacked layout is what lets all islands execute in ONE batched
    Pallas launch (kernels/dict_ops.scan_filter_agg_sharded) instead of a
    serial per-shard loop.

    Provenance is explicit: ``version`` is the source column's update
    round and ``snapshot_id`` the consistency snapshot it was pinned from
    (-1 for ad-hoc views). `invalidate` marks the view stale;
    every consumer calls `require_fresh` first, so a swapped-out view is
    a hard `StaleShardedViewError`, not a silent cache hit.
    """

    codes: jnp.ndarray        # (n_shards, width) int32, padded slots = 0
    valid: jnp.ndarray        # (n_shards, width) bool, padded slots = False
    dictionary: jnp.ndarray   # replicated across islands
    bounds: tuple[int, ...]   # row partition, len n_shards + 1
    version: int
    snapshot_id: int = -1
    stale_reason: str | None = None
    # Join build side, materialized lazily by `dict_counts` and owned by
    # the view: a Phase-2 swap or GC invalidates the view and the cached
    # build dies with it (`require_fresh` guards every read).
    _dict_counts: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def n_shards(self) -> int:
        return len(self.bounds) - 1

    @property
    def n_rows(self) -> int:
        return self.bounds[-1]

    @property
    def width(self) -> int:
        return int(self.codes.shape[1])

    @property
    def sizes(self) -> tuple[int, ...]:
        return tuple(hi - lo for lo, hi in zip(self.bounds, self.bounds[1:]))

    # priced by the cost model exactly like the column it mirrors
    @property
    def dict_size(self) -> int:
        return int(self.dictionary.shape[0])

    @property
    def bit_width(self) -> int:
        return max(1, math.ceil(math.log2(max(self.dict_size, 2))))

    @property
    def encoded_bytes(self) -> float:
        return self.n_rows * self.bit_width / 8.0

    @property
    def stale(self) -> bool:
        return self.stale_reason is not None

    def invalidate(self, reason: str) -> None:
        self.stale_reason = reason

    def require_fresh(self) -> None:
        if self.stale_reason is not None:
            raise StaleShardedViewError(
                f"sharded view of column version {self.version} "
                f"(snapshot {self.snapshot_id}) is stale: "
                f"{self.stale_reason}")

    def dict_counts(self) -> np.ndarray:
        """Per-dictionary-value occurrence counts of the view's valid rows.

        This is a hash join's *build side* (the replicated dictionary's
        occurrence histogram): it depends only on the pinned data, so it is
        computed once per view — across all islands' resident shards — and
        reused by every join-query group that probes against this view,
        instead of being re-histogrammed per call. Callers must treat the
        returned array as read-only.
        """
        self.require_fresh()
        if self._dict_counts is None:
            codes = np.asarray(self.codes)
            valid = np.asarray(self.valid)
            count = np.zeros(self.dict_size, dtype=np.int64)
            for s in range(self.n_shards):
                count += np.bincount(codes[s][valid[s]],
                                     minlength=self.dict_size
                                     ).astype(np.int64)
            self._dict_counts = count
        return self._dict_counts

    def shard(self, s: int) -> EncodedColumn:
        """One island's resident shard as an (unpadded) EncodedColumn."""
        self.require_fresh()
        size = self.bounds[s + 1] - self.bounds[s]
        return EncodedColumn(codes=self.codes[s, :size],
                             dictionary=self.dictionary,
                             valid=self.valid[s, :size],
                             version=self.version)

    def to_column(self) -> EncodedColumn:
        """Reassemble the full column (row-order inverse of the shard)."""
        return concat_columns([self.shard(s) for s in range(self.n_shards)])


def make_sharded_view(col: EncodedColumn, n_shards: int,
                      snapshot_id: int = -1) -> ShardedView:
    """Shard `col` ONCE into a resident ShardedView (the pin-time copy).

    This is the only place the snapshot plane moves rows: operators after
    this consume the stacked arrays directly, so a query round shards each
    pinned column exactly once instead of re-partitioning per operator.
    """
    bounds = shard_bounds(col.n_rows, n_shards)
    sizes = [hi - lo for lo, hi in zip(bounds, bounds[1:])]
    width = max(sizes, default=0)
    codes = np.zeros((n_shards, width), dtype=np.int32)
    valid = np.zeros((n_shards, width), dtype=bool)
    src_codes = np.asarray(col.codes)
    src_valid = np.asarray(col.valid)
    for s, (lo, hi) in enumerate(zip(bounds, bounds[1:])):
        codes[s, :hi - lo] = src_codes[lo:hi]
        valid[s, :hi - lo] = src_valid[lo:hi]
    return ShardedView(codes=codes, valid=valid,
                       dictionary=col.dictionary, bounds=tuple(bounds),
                       version=col.version, snapshot_id=snapshot_id)


def stack_shard_columns(shard_cols: list[EncodedColumn],
                        snapshot_id: int = -1) -> ShardedView:
    """Adopt per-island shard columns as a ShardedView directly.

    The Phase-2 sibling of `make_sharded_view`: update application
    produces each island's freshly applied shard as its own
    `EncodedColumn`, and on placements with per-island residency those
    shards should become the next round's resident view *without* a
    concat + re-split round trip through one flat column. Shards must
    line up with `shard_bounds` (they do by construction — update routing
    partitions by the same bounds) and must share a dictionary and
    version, exactly `concat_columns`'s mixing check.
    """
    if not shard_cols:
        raise ValueError("stack_shard_columns needs at least one shard")
    head = shard_cols[0]
    for s in shard_cols[1:]:
        if s.version != head.version:
            raise ValueError(
                f"shard version mismatch: {s.version} != {head.version}")
        if s.dictionary is not head.dictionary and not (
                s.dictionary.shape == head.dictionary.shape
                and bool(jnp.array_equal(s.dictionary, head.dictionary))):
            raise ValueError("shard dictionary mismatch (different rounds?)")
    sizes = [c.n_rows for c in shard_cols]
    n_rows = sum(sizes)
    bounds = shard_bounds(n_rows, len(shard_cols))
    if [hi - lo for lo, hi in zip(bounds, bounds[1:])] != sizes:
        raise ValueError(
            f"shard sizes {sizes} do not match the shard_bounds partition "
            f"of {n_rows} rows over {len(shard_cols)} islands")
    width = max(sizes, default=0)
    codes = np.zeros((len(shard_cols), width), dtype=np.int32)
    valid = np.zeros((len(shard_cols), width), dtype=bool)
    for s, col in enumerate(shard_cols):
        codes[s, :col.n_rows] = np.asarray(col.codes)
        valid[s, :col.n_rows] = np.asarray(col.valid)
    return ShardedView(codes=codes, valid=valid,
                       dictionary=head.dictionary, bounds=tuple(bounds),
                       version=head.version, snapshot_id=snapshot_id)


def host_column(col) -> EncodedColumn:
    """``col`` as an EncodedColumn of host numpy arrays, fetched on demand.

    A replica column whose islands live on their own devices is a
    `ShardedView`; this reassembles its shards in row order. Checkpoints,
    resizes and the join build side read host codes through it."""
    if isinstance(col, ShardedView):
        codes, valid = np.asarray(col.codes), np.asarray(col.valid)
        return EncodedColumn(
            codes=np.concatenate([codes[s, :n]
                                  for s, n in enumerate(col.sizes)]),
            dictionary=np.asarray(col.dictionary),
            valid=np.concatenate([valid[s, :n]
                                  for s, n in enumerate(col.sizes)]),
            version=col.version)
    return EncodedColumn(codes=np.asarray(col.codes),
                         dictionary=np.asarray(col.dictionary),
                         valid=np.asarray(col.valid), version=col.version)


@dataclasses.dataclass
class DSMReplica:
    """The analytical island's replica: one EncodedColumn per table column,
    or, where each island's shard lives on its own device, one resident
    `ShardedView` (`host_column` fetches its codes)."""

    columns: dict[int, EncodedColumn]

    @classmethod
    def from_table(cls, table: np.ndarray) -> "DSMReplica":
        return cls(columns={j: encode_column(table[:, j]) for j in range(table.shape[1])})

    def to_table(self) -> np.ndarray:
        cols = [np.asarray(decode_column(self.columns[j])) for j in sorted(self.columns)]
        return np.stack(cols, axis=1)

    @property
    def n_rows(self) -> int:
        return next(iter(self.columns.values())).n_rows

    @property
    def n_cols(self) -> int:
        return len(self.columns)

    @property
    def encoded_bytes(self) -> float:
        return sum(c.encoded_bytes for c in self.columns.values())
