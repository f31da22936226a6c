"""Polynesia's consistency mechanism (§6): column-grain snapshot chains.

Key ideas reproduced exactly:
  * snapshot chains are per *column*, not per tuple (unlike MVCC),
  * lazy (late-materialization) snapshotting: updates only mark a column
    dirty; a snapshot is created when an analytical query arrives AND the
    column is dirty AND no current snapshot exists (snapshot sharing),
  * analytics read the chain head frozen at query start — no chain
    traversal, no timestamp comparisons,
  * GC: when a query finishes, snapshots with no readers are deleted
    (except the chain head),
  * updates always go straight to the main replica via the two-phase
    update application (Phase 2 = atomic pointer swap, here a functional
    replacement), so freshness never waits on readers.

The copy unit (multiple fetch/writeback engines + hash-indexed tracking
buffer) is priced as vault-local bandwidth (`resource="copy"`); the Pallas
analog is kernels/snapshot_copy.
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from repro.core.application import _apply_row_ops, _split_ops
from repro.core.backend import get_backend
from repro.core.dsm import (DSMReplica, EncodedColumn, ShardedView,
                            concat_columns, new_values)
from repro.core.hwmodel import CostLog
from repro.core.schema import VALUE_BYTES


class BuildSide:
    """A self-join's build side for one replica column: each dictionary
    code's count over the valid rows (int64), kept for a column whose
    codes live on the device, so that no join reads them back.

    It follows the column through its Phase-2 swaps from the applied
    batches alone, on a host copy of the column's values and validity: an
    apply inserts a zero count at each new value's place in the merged
    dictionary, takes each touched row's old value off its count and
    puts its new value on. Each version's counts are an array of their
    own, so a snapshot keeps the counts of the version it pinned.
    """

    def __init__(self, col: EncodedColumn):
        codes = np.asarray(col.codes)
        self.dictionary = np.asarray(col.dictionary)
        self.values = self.dictionary[codes]
        self.valid = np.array(col.valid, dtype=bool)
        self.counts = np.bincount(codes[self.valid],
                                  minlength=len(self.dictionary))
        self.version = col.version

    def follow(self, new_col: EncodedColumn, updates: np.ndarray) -> None:
        """Move to ``new_col``, this column after applying ``updates``."""
        new_dict = np.asarray(new_col.dictionary)
        mods, ins, dels = _split_ops(updates)
        thresholds, _ = new_values(self.dictionary, np.unique(
            np.concatenate([mods["value"], ins["value"]])))
        counts = np.insert(self.counts, thresholds, 0)
        rows = np.unique(updates["row"])
        self._count(counts, new_dict, rows[rows < len(self.values)], -1)
        self.values, self.valid = _apply_row_ops(
            self.values.astype(new_dict.dtype, copy=False), self.valid,
            new_dict, mods, ins, dels, encode=lambda v: v)
        self._count(counts, new_dict, rows, 1)
        self.dictionary, self.counts = new_dict, counts
        self.version = new_col.version

    def _count(self, counts, dictionary, rows, step: int) -> None:
        live = rows[self.valid[rows]]
        np.add.at(counts, np.searchsorted(dictionary, self.values[live]),
                  step)


@dataclasses.dataclass
class _Version:
    version_id: int
    column: EncodedColumn
    readers: int = 0
    # the column's `BuildSide` counts at this version, where it keeps one
    build_counts: np.ndarray | None = None
    # The sharded snapshot plane: islands' resident shards of this
    # version, materialized once at first pinned read (`read_scan`) and
    # reused by every query group pinning the same version. Invalidated —
    # a hard StaleShardedViewError for any later use — when the version is
    # garbage-collected or swapped out unpinned (see on_update).
    view: ShardedView | None = None

    def drop_view(self, reason: str) -> None:
        if self.view is not None:
            self.view.invalidate(reason)
            self.view = None


class SnapshotChain:
    """Chain of column versions; head = most recent snapshot."""

    def __init__(self, col_id: int):
        self.col_id = col_id
        self.versions: list[_Version] = []
        self.dirty = True  # no snapshot exists yet

    @property
    def head(self) -> _Version | None:
        return self.versions[-1] if self.versions else None

    def gc(self) -> int:
        """Drop versions with no readers, keeping the chain head. Returns #freed.

        Ordering: the head survives unconditionally (it is the share target
        for the next query), every older version survives only while
        pinned, and the kept versions are re-sorted by version id so the
        chain stays oldest-to-newest — `head` must remain the most recent
        snapshot regardless of the order readers finished in.
        """
        keep = self.versions[-1:]
        freed = 0
        for v in self.versions[:-1]:
            if v.readers > 0:
                keep.append(v)
            else:
                freed += 1
                v.drop_view(f"snapshot {v.version_id} of column "
                            f"{self.col_id} was garbage-collected")
        keep.sort(key=lambda v: v.version_id)
        self.versions = keep
        return freed


class ConsistencyManager:
    """Snapshot-isolation for analytics over a DSMReplica (§6)."""

    def __init__(self, replica: DSMReplica, cost: CostLog | None = None,
                 on_pim: bool = True, backend=None):
        self.replica = replica
        self.cost = cost
        self.on_pim = on_pim
        self.backend = get_backend(backend)
        self.chains = {c: SnapshotChain(c) for c in replica.columns}
        self._version_ids = itertools.count()
        self._handles: dict[int, dict[int, _Version]] = {}
        self._handle_ids = itertools.count()
        self.snapshots_created = 0
        self.snapshots_shared = 0
        self.views_built = 0
        self.views_shared = 0
        self.views_resident = 0
        # Phase-2 residency handoff (mesh placement): the freshly applied
        # per-island shard columns, installed directly as a device-resident
        # ShardedView by `on_update_shards` and adopted by the next pinned
        # `read_scan` — so mesh islands keep their shards resident across
        # rounds instead of round-tripping concat + re-shard through the
        # host. One pending view per column; superseded by the next swap.
        self._resident: dict[int, ShardedView] = {}
        # self-join build sides of the columns that keep one
        # (`keep_build_sides`), each following its column's swaps
        self.build_sides: dict[int, BuildSide] = {}

    def keep_build_sides(self) -> None:
        """Keep a `BuildSide` of every replica column from now on: a
        replica whose codes live on the device then joins without reading
        them back. A swap given no update batch drops the column's."""
        self.build_sides = {c: BuildSide(col)
                            for c, col in self.replica.columns.items()}

    # -- transactional side ----------------------------------------------
    def on_update(self, col_id: int, new_col: EncodedColumn,
                  updates=None) -> None:
        """Phase-2 pointer swap: install the new column, mark dirty.
        ``updates``, the batch applied, moves the column's build side.

        The swap also invalidates every *unpinned* ShardedView of this
        column's snapshots: the next pinned read will snapshot + re-shard
        the fresh column, and using a swapped-out view without a pin is a
        hard StaleShardedViewError (never a silently stale cache). Views
        still pinned by in-flight queries stay valid — that is snapshot
        isolation — until their readers finish and GC drops the version.
        """
        side = self.build_sides.get(col_id)
        if side is not None:
            if updates is None:
                del self.build_sides[col_id]  # a swap it cannot follow
            else:
                side.follow(new_col, updates)
        self.replica.columns[col_id] = new_col
        self.chains[col_id].dirty = True
        self._resident.pop(col_id, None)  # superseded before adoption
        for v in self.chains[col_id].versions:
            if v.readers == 0:
                v.drop_view(f"column {col_id} was swapped out by a Phase-2 "
                            f"update (now at version {new_col.version})")

    def on_update_shards(self, col_id: int,
                         shard_cols: list[EncodedColumn]) -> None:
        """Phase-2 pointer swap for a sharded replica, all-or-none.

        A round's update application produces one new column per analytical
        island; queries must never observe a replica where some islands show
        the new round and others the old. The swap therefore validates the
        *complete* shard set (count matches the backend's island count,
        shards share one dictionary and version — `concat_columns` rejects
        mixed rounds) before a single atomic pointer install. On any
        validation failure the replica is left untouched.
        """
        expected = getattr(self.backend, "n_shards", 1)
        if len(shard_cols) != expected:
            raise ValueError(
                f"partial shard set for column {col_id}: got "
                f"{len(shard_cols)} shards, backend has {expected} islands")
        new_col = concat_columns(shard_cols)  # rejects mixed-round shards
        self.on_update(col_id, new_col)
        place = getattr(self.backend, "place_shards", None)
        if place is not None:
            # Mesh placement: the swap IS the residency install — each
            # island's freshly applied shard is device_put to its own
            # device here, and the next pinned read adopts the view
            # (read_scan) instead of re-sharding through the host.
            self._resident[col_id] = place(shard_cols)

    def rebind_backend(self, backend) -> None:
        """Re-point the snapshot plane at a resized backend (elastic
        resharding, core/elastic.py) — all-or-none, like the Phase-2 swap.

        Every *unpinned* `ShardedView` of every chain is invalidated in one
        pass (a view partitioned for the old island count must never serve
        another scan — using one is a hard StaleShardedViewError, never a
        silently mis-sharded read), pending residency installs are dropped,
        and the new backend takes over snapshot/shard/placement duties. The
        replica columns and the snapshot chains themselves are untouched:
        the next pinned `read_scan` re-shards the pinned version under the
        new partition. Refuses to run with pinned queries in flight — a
        resize happens between query batches, where `_handles` is empty.
        """
        if self._handles:
            raise RuntimeError(
                f"cannot rebind the consistency backend with "
                f"{len(self._handles)} pinned query handle(s) in flight; "
                "finish the query batch first")
        new_be = get_backend(backend)
        old_n = getattr(self.backend, "n_shards", 1)
        new_n = getattr(new_be, "n_shards", 1)
        for chain in self.chains.values():
            for v in chain.versions:
                v.drop_view(
                    f"column {chain.col_id}'s analytical islands were "
                    f"resized ({old_n} -> {new_n} shards); re-pin to scan "
                    "the new partition")
        self._resident.clear()
        self.backend = new_be

    # -- analytical side ---------------------------------------------------
    def _snapshot(self, col_id: int) -> _Version:
        col = self.replica.columns[col_id]
        # Copy-unit snapshot on the execution backend: the NumpyBackend
        # aliases (JAX arrays are immutable, so aliasing IS a consistent
        # snapshot), the PallasBackend streams the codes through the
        # kernels/snapshot_copy copy unit, carrying chunks that are clean
        # relative to the previous chain head. Either way the copy the
        # hardware would do is priced below and the chain is bumped.
        head = self.chains[col_id].head
        snap = self.backend.snapshot_column(
            col, prev=head.column if head is not None else None)
        side = self.build_sides.get(col_id)
        v = _Version(version_id=next(self._version_ids), column=snap,
                     build_counts=(side.counts if side is not None
                                   and side.version == col.version
                                   else None))
        self.chains[col_id].versions.append(v)
        self.chains[col_id].dirty = False
        self.snapshots_created += 1
        if self.cost is not None:
            nbytes = col.encoded_bytes + col.dict_size * VALUE_BYTES
            # timeline metadata: snapshot volume on this node (one call per
            # pinned dirty column, hence the accumulating annotate)
            self.cost.annotate_add(n_snapshots=1, snapshot_bytes=2 * nbytes)
            if self.on_pim:
                self.cost.add(phase="snapshot", island="ana", resource="copy",
                              bytes_local=2 * nbytes)
            else:
                self.cost.add(phase="snapshot", island="txn", resource="cpu",
                              cycles=nbytes * 0.5, bytes_offchip=2 * nbytes)
        return v

    def begin_query(self, col_ids: list[int]) -> int:
        """Pin a consistent snapshot of the given columns; returns a handle."""
        pinned: dict[int, _Version] = {}
        for c in col_ids:
            chain = self.chains[c]
            if chain.dirty or chain.head is None:
                v = self._snapshot(c)
            else:
                v = chain.head
                self.snapshots_shared += 1
            v.readers += 1
            pinned[c] = v
        h = next(self._handle_ids)
        self._handles[h] = pinned
        return h

    def read(self, handle: int, col_id: int) -> EncodedColumn:
        """Read the pinned version — O(1), no chain traversal (vs MVCC)."""
        return self._handles[handle][col_id].column

    def build_counts(self, handle: int, col_id: int) -> np.ndarray | None:
        """The pinned version's self-join build side (`BuildSide`), or
        None where the column keeps none."""
        return self._handles[handle][col_id].build_counts

    def read_scan(self, handle: int, col_id: int):
        """Pinned read for the scan plane: shard at pin, once per round.

        On a sharded backend this returns the pinned version's resident
        `ShardedView`, materializing it on first access ("shard at pin")
        and reusing it for every later query group that pins the same
        snapshot version — so a round shards each column exactly once, and
        all islands scan their resident shards in one batched launch. On
        single-replica backends it is `read` (the plain pinned column).
        """
        v = self._handles[handle][col_id]
        if (getattr(self.backend, "n_shards", 1) <= 1
                and getattr(self.backend, "placement", "stacked") != "mesh"):
            return v.column
        if v.view is None or v.view.stale:
            resident = self._resident.pop(col_id, None)
            if (resident is not None and not resident.stale
                    and resident.version == v.column.version):
                # adopt the Phase-2 residency install (mesh placement):
                # the islands' devices already hold these shards
                resident.snapshot_id = v.version_id
                v.view = resident
                self.views_resident += 1
            else:
                v.view = self.backend.shard_view(v.column,
                                                 snapshot_id=v.version_id)
                self.views_built += 1
        else:
            self.views_shared += 1
        return v.view

    def pin_scan_group(self, col_sets: list[list[int]]
                       ) -> tuple[list[int], dict]:
        """Pin one snapshot handle per query of a fused same-column-set
        group and materialize the group's shared scan view.

        Every query still pins its own handle (reader counts drive GC
        exactly as with per-query `begin_query` calls), but because no
        update lands between the pins, all handles resolve to the same
        snapshot versions — the group reads one consistent `read_scan`
        view, sharded once per round on island backends. Returns
        ``(handles, {col_id: column-or-ShardedView})``; callers must
        `end_query` every handle when the group finishes.
        """
        handles = [self.begin_query(cols) for cols in col_sets]
        view = {c: self.read_scan(handles[0], c) for c in col_sets[0]}
        return handles, view

    def end_query(self, handle: int) -> None:
        pinned = self._handles.pop(handle)
        for c, v in pinned.items():
            v.readers -= 1
            self.chains[c].gc()

    # -- stats -------------------------------------------------------------
    def chain_lengths(self) -> dict[int, int]:
        return {c: len(ch.versions) for c, ch in self.chains.items()}
