"""Pluggable execution backends: numpy reference vs Pallas accelerator units.

Polynesia's speedups come from specialized in-memory hardware; this repo
models those units as Pallas kernels. The hot path (engine, shipping,
update application, consistency) is written against the small operator
surface below, so the same drivers can run either on

* ``NumpyBackend`` — the original pure-numpy code paths, extracted here as
  the functional reference, or
* ``PallasBackend`` — dispatching each operator to its hardware-analog
  kernel (compiled on TPU/GPU, jitted jax-numpy lowering on CPU, Pallas
  interpret mode on demand — ``kernels.common.kernel_mode``), or
* ``ShardedBackend`` — N analytical islands, each owning a row-wise DSM
  shard, fanning scans out over any inner backend and reducing the exact
  partial aggregates (spec ``"pallas@4"``, ``n_shards=`` on the drivers,
  or the ``REPRO_SHARDS`` environment variable), or
* ``MeshBackend`` — the same N islands laid one-per-DEVICE on a 1-D
  `jax.Mesh` (spec ``"pallas@4/mesh"``, ``placement="mesh"``, or the
  ``REPRO_PLACEMENT`` environment variable): every island's resident
  shard lives on its own device, one ``shard_map`` launch scans all
  islands in place, and the cross-island reduction runs ON the mesh as
  an integer ``psum``:

    ==========================  =================================
    operator                    kernel
    ==========================  =================================
    filter + aggregate          kernels/dict_ops.scan_filter_agg
                                (+ _batch for fused multi-query)
    hash join / value encode    kernels/hash_probe.build_table/probe
    update-log / dict merge     kernels/merge_runs
    update-dictionary sort      kernels/bitonic_sort
    snapshot copy               kernels/snapshot_copy
    stage-3 re-encode           kernels/reencode (device-resident
                                columns only)
    ==========================  =================================

Every backend must produce *bit-identical* results: the integer query
answers, merged logs, dictionaries and snapshots are asserted equal across
backends in tests/test_backend.py. Selection is by spec — a ``BackendSpec``
or its string form ``name[@N][/placement]`` (``backend="pallas@4/mesh"``
threaded through the system drivers), by instance, or globally via
``set_default_backend`` / the ``REPRO_BACKEND`` environment variable.
"""

from __future__ import annotations

import abc
import contextlib
import dataclasses
import functools
import os
import sys
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dsm import (EncodedColumn, ShardedView, make_sharded_view,
                            new_values, stack_shard_columns)
from repro.core.hwmodel import span
from repro.core.nsm import UPDATE_DTYPE
from repro.distributed import island_mesh, place_shard_arrays
from repro.kernels.bitonic_sort import sort_1024, sort_rows
from repro.kernels.common import width_bucket
from repro.kernels.dict_ops import (apply_pipeline_batch, scan_filter_agg,
                                    scan_filter_agg_batch,
                                    scan_filter_agg_group,
                                    scan_filter_agg_group_sharded,
                                    scan_filter_agg_mesh,
                                    scan_filter_agg_sharded, scan_values_agg,
                                    scan_values_delta)
from repro.kernels.hash_probe import (EMPTY_KEY, build_table, probe,
                                      probe_sharded, scan_filter_agg_join,
                                      scan_filter_agg_join_group,
                                      scan_filter_agg_join_mesh,
                                      scan_filter_agg_join_sharded)
from repro.kernels.merge_runs import merge_sorted_pairs, merge_sorted_runs
from repro.kernels.reencode import reencode_rows, reencode_rows_mesh
from repro.kernels.snapshot_copy import (dirty_chunks, snapshot_copy,
                                         snapshot_copy_mesh)

SNAPSHOT_BLOCK = 8192  # copy-unit chunk size (kernels/snapshot_copy default)
# The largest old dictionary the device merge networks take. A merge
# network holds a whole (8, width) row tile in VMEM and unrolls log2(width)
# stages, so its compile grows with the width and stops fitting VMEM at a
# 2^17-wide merge (a 2^16 old dictionary); larger dictionaries merge by
# insertion at their new values' thresholds (`_insert_stages`).
MERGE_NETWORK_MAX_DICT = 4096

# Every kernel entry point this module dispatches to, by the module-global
# name used at the call site. The kernel-call counters (the tests'
# monkeypatch wrappers and `counting_kernel_calls` below, which feeds the
# CI launch-count gate) wrap exactly these names — keep it next to the
# imports so adding a kernel here keeps the gate honest.
KERNEL_ENTRY_POINTS = ("scan_filter_agg", "scan_filter_agg_batch",
                       "scan_filter_agg_group",
                       "scan_filter_agg_group_sharded",
                       "scan_filter_agg_sharded", "scan_filter_agg_mesh",
                       "scan_filter_agg_join",
                       "scan_filter_agg_join_group",
                       "scan_filter_agg_join_sharded",
                       "scan_filter_agg_join_mesh", "probe",
                       "probe_sharded", "build_table", "merge_sorted_runs",
                       "merge_sorted_pairs", "sort_1024", "sort_rows",
                       "snapshot_copy", "scan_values_agg",
                       "scan_values_delta", "apply_pipeline_batch",
                       "reencode_rows", "reencode_rows_mesh",
                       "snapshot_copy_mesh", "dirty_chunks")
# The entry points among them that scan columns for a query group: each
# call is a ``scan`` span of the recording CostLog (`_scan_span`).
SCAN_ENTRY_POINTS = ("scan_filter_agg", "scan_filter_agg_batch",
                     "scan_filter_agg_group",
                     "scan_filter_agg_group_sharded",
                     "scan_filter_agg_sharded", "scan_filter_agg_mesh",
                     "scan_filter_agg_join", "scan_filter_agg_join_group",
                     "scan_filter_agg_join_sharded",
                     "scan_filter_agg_join_mesh", "scan_values_agg",
                     "scan_values_delta")


def _scan_span(entry_point):
    """A scan entry point whose every call, from dispatch through argument
    transfer to the host integers it returns, is a ``scan`` span of the
    recording CostLog (`hwmodel.span`)."""
    @functools.wraps(entry_point)
    def call(*args, **kwargs):
        with span("scan"):
            return entry_point(*args, **kwargs)
    return call


for _name in SCAN_ENTRY_POINTS:
    globals()[_name] = _scan_span(globals()[_name])


@contextlib.contextmanager
def counting_kernel_calls():
    """Count kernel dispatches per entry point while the context is open.

    Yields a dict {entry_point_name: calls}; the wrappers are removed on
    exit. This is the canonical counter behind the CI launch gate
    (benchmarks/run.py ci -> tools/check_bench.py); the test suites use
    pytest's monkeypatch over the same KERNEL_ENTRY_POINTS list.
    """
    module = sys.modules[__name__]
    counts: dict[str, int] = {}
    saved = {name: getattr(module, name) for name in KERNEL_ENTRY_POINTS}

    def wrap(name, real):
        def inner(*args, **kwargs):
            counts[name] = counts.get(name, 0) + 1
            return real(*args, **kwargs)
        return inner

    for name, real in saved.items():
        setattr(module, name, wrap(name, real))
    try:
        yield counts
    finally:
        for name, real in saved.items():
            setattr(module, name, real)


class ExecutionBackend(abc.ABC):
    """Operator surface the HTAP hot path is written against.

    Methods take/return host (numpy) values and EncodedColumns; backends are
    free to stage through device arrays internally. All results must be
    exact — equality across backends is part of the contract, not a tolerance.
    """

    name: str = "?"
    # How analytical islands are laid out: "stacked" (leading-axis batch on
    # one device — the flat backends trivially so) or "mesh" (one island
    # per device of a jax.Mesh — MeshBackend).
    placement: str = "stacked"

    # -- analytical engine (§7) -------------------------------------------
    def code_range(self, col: EncodedColumn, lo: int, hi: int) -> tuple[int, int]:
        """Value range -> code range through the order-preserving dictionary."""
        d = np.asarray(col.dictionary)
        return (int(np.searchsorted(d, lo, side="left")),
                int(np.searchsorted(d, hi, side="right")))

    @abc.abstractmethod
    def filter_mask(self, col: EncodedColumn, lo: int, hi: int) -> np.ndarray:
        """Boolean row mask for lo <= value <= hi (dictionary pushdown)."""

    @abc.abstractmethod
    def filter_agg(self, fcol: EncodedColumn, acol: EncodedColumn,
                   lo: int, hi: int) -> tuple[int, int]:
        """(sum of acol values, selected-row count) over the filter range."""

    @abc.abstractmethod
    def filter_agg_batch(self, fcol: EncodedColumn, acol: EncodedColumn,
                         bounds: Sequence[tuple[int, int]]
                         ) -> list[tuple[int, int]]:
        """Fused multi-query scan: one pass answering all (lo, hi) bounds."""

    def filter_agg_mask(self, fcol: EncodedColumn, acol: EncodedColumn,
                        lo: int, hi: int) -> tuple[int, int, np.ndarray]:
        """filter_agg plus the row mask (needed by join queries). Backends
        that fuse the aggregate (so the mask is not a by-product) get it
        from one extra filter_mask pass."""
        s, c = self.filter_agg(fcol, acol, lo, hi)
        return s, c, self.filter_mask(fcol, lo, hi)

    @abc.abstractmethod
    def hash_join_count(self, left: EncodedColumn, right: EncodedColumn,
                        left_mask: np.ndarray | None = None) -> int:
        """|left JOIN right on value| via dictionary-level hash matching."""

    def filter_agg_join_batch(self, fcol: EncodedColumn, acol: EncodedColumn,
                              jcol: EncodedColumn,
                              bounds: Sequence[tuple[int, int]],
                              rcount: np.ndarray | None = None
                              ) -> list[tuple[int, int, int]]:
        """Fused join-query group: for every (lo, hi) predicate return the
        exact ``(sum, count, self_join_count)`` triple, where the join count
        is ``|jcol JOIN jcol|`` restricted to the predicate's row mask.

        ``rcount`` overrides the build-side per-code occurrence histogram
        (the delta-merged read passes the overlay-corrected histogram; the
        probe side is corrected separately in the engine). The identity
        ``hash_join_count(j, j, mask) == sum(rcount[jcodes[mask & jvalid]])``
        makes the override exact.

        This default is the original per-query host path (mask-producing
        scan + dictionary-level hash join), kept as the reference; the
        accelerator backends override it with ONE fused device call per
        group (the join reduces to a second exact scan against the build
        side's occurrence histogram — see kernels/hash_probe)."""
        out = []
        rc = None if rcount is None else np.asarray(rcount, dtype=np.int64)
        for lo, hi in bounds:
            s, c, mask = self.filter_agg_mask(fcol, acol, lo, hi)
            if rc is None:
                j = self.hash_join_count(jcol, jcol, left_mask=mask)
            else:
                keep = mask & np.asarray(jcol.valid)
                j = int(rc[np.asarray(jcol.codes)[keep]].sum())
            out.append((s, c, j))
        return out

    def filter_agg_values_batch(self, fvals, avals, valid,
                                bounds: Sequence[tuple[int, int]]
                                ) -> list[tuple[int, int]]:
        """Fused multi-query scan over RAW (decoded) rows — the delta-store
        correction pass. bounds are INCLUSIVE value ranges (the overlay
        carries values, so there is no dictionary to push predicates into);
        returns exact [(sum, count), ...]. This default is the numpy
        reference; PallasBackend dispatches the split-accumulator kernel."""
        fvals = np.asarray(fvals)
        valid = np.asarray(valid) != 0
        avals = np.asarray(avals, dtype=np.int64)
        out = []
        for lo, hi in bounds:
            mask = (fvals >= lo) & (fvals <= hi) & valid
            out.append((int(avals[mask].sum()), int(mask.sum())))
        return out

    def filter_agg_values_delta(self, corr, bounds: Sequence[tuple[int, int]]
                                ) -> list[tuple[int, int]]:
        """Effective-minus-base correction of one overlay stack: per bound,
        the exact (Δsum, Δcount) a delta overlay contributes on top of the
        base scan. ``corr`` is a (6, nr) int32 stack of
        [fv_eff, av_eff, valid_eff, fv_base, av_base, valid_base] rows (the
        touched-row union's effective and base states — engine._corr_stack).
        This default is two raw-value scans subtracted on the host;
        PallasBackend fuses both into ONE launch (scan_values_delta)."""
        corr = np.asarray(corr)
        eff = self.filter_agg_values_batch(corr[0], corr[1], corr[2], bounds)
        base = self.filter_agg_values_batch(corr[3], corr[4], corr[5], bounds)
        return [(e[0] - b[0], e[1] - b[1]) for e, b in zip(eff, base)]

    def filter_agg_delta_batch(self, fcol: EncodedColumn, acol: EncodedColumn,
                               bounds: Sequence[tuple[int, int]], corr
                               ) -> list[tuple[int, int]]:
        """Fused multi-query scan over the pinned base WITH the delta-store
        overlay correction folded in: ``filter_agg_batch`` answers plus the
        ``corr`` stack's per-bound deltas. This default composes the two
        existing operators (the reference algebra); PallasBackend runs base
        scan and both correction scans as ONE traced launch
        (scan_filter_agg_group)."""
        fused = self.filter_agg_batch(fcol, acol, bounds)
        if corr is None:
            return fused
        deltas = self.filter_agg_values_delta(corr, bounds)
        return [(s + ds, c + dc)
                for (s, c), (ds, dc) in zip(fused, deltas)]

    def filter_agg_join_delta_batch(self, fcol: EncodedColumn,
                                    acol: EncodedColumn, jcol: EncodedColumn,
                                    bounds: Sequence[tuple[int, int]],
                                    rcount, corr_a, corr_j
                                    ) -> list[tuple[int, int, int]]:
        """Delta-merged join group: ``filter_agg_join_batch`` with the
        EFFECTIVE build-side histogram override plus the aggregate
        (``corr_a``) and weighted probe-row (``corr_j``) overlay
        corrections — ``corr_j``'s value lanes carry the effective join-
        histogram weights and only its sum delta applies (the join term).
        Either stack may be None. PallasBackend overrides with ONE fused
        launch (scan_filter_agg_join_group)."""
        fused = self.filter_agg_join_batch(fcol, acol, jcol, bounds,
                                           rcount=rcount)
        if corr_a is not None:
            da = self.filter_agg_values_delta(corr_a, bounds)
            fused = [(s + ds, c + dc, j)
                     for (s, c, j), (ds, dc) in zip(fused, da)]
        if corr_j is not None:
            dj = self.filter_agg_values_delta(corr_j, bounds)
            fused = [(s, c, j + djs)
                     for (s, c, j), (djs, _) in zip(fused, dj)]
        return fused

    def scan_view(self, fview: ShardedView, aview: ShardedView,
                  code_bounds: Sequence[tuple[int, int]]
                  ) -> list[list[tuple[int, int]]]:
        """Every island's fused multi-predicate scan over resident shards.

        Consumes the stacked ShardedView arrays (the snapshot plane's
        pin-time copies) and returns exact per-island partials:
        ``[[(sum, count), ...per predicate] ...per shard]``. This default
        is the serial per-shard reference — a host loop over unpadded
        shard slices, kept as the oracle the batched kernel path must
        match bit-for-bit. Accelerator backends override it with ONE
        batched launch over the leading shard axis.
        """
        fview.require_fresh()
        aview.require_fresh()
        fcodes = np.asarray(fview.codes)
        fvalid = np.asarray(fview.valid)
        acodes = np.asarray(aview.codes)
        adict = np.asarray(aview.dictionary, dtype=np.int64)
        out = []
        for s, size in enumerate(fview.sizes):
            fc, va, ac = fcodes[s, :size], fvalid[s, :size], acodes[s, :size]
            res = []
            for code_lo, code_hi in code_bounds:
                mask = (fc >= code_lo) & (fc < code_hi) & va
                counts = np.bincount(ac[mask], minlength=aview.dict_size)
                res.append((int(counts @ adict), int(mask.sum())))
            out.append(res)
        return out

    def scan_view_join(self, fview: ShardedView, aview: ShardedView,
                       jview: ShardedView,
                       code_bounds: Sequence[tuple[int, int]],
                       rcount: np.ndarray | None = None
                       ) -> list[list[tuple[int, int, int]]]:
        """Every island's fused join-group scan over resident shards.

        Like `scan_view` but each predicate also yields the island's partial
        self-join count: its resident probe-side rows against the GLOBAL
        build-side histogram (``jview.dict_counts()`` — the replicated
        dictionary's occurrence counts over ALL islands, overridable via
        ``rcount`` for the delta-merged read), so the cross-shard reduction
        is a plain exact sum. This default is the serial per-shard numpy
        reference; PallasBackend overrides it with ONE batched launch.
        """
        fview.require_fresh()
        aview.require_fresh()
        jview.require_fresh()
        fcodes = np.asarray(fview.codes)
        fvalid = np.asarray(fview.valid)
        acodes = np.asarray(aview.codes)
        adict = np.asarray(aview.dictionary, dtype=np.int64)
        jcodes = np.asarray(jview.codes)
        jvalid = np.asarray(jview.valid)
        rcount = (jview.dict_counts() if rcount is None
                  else np.asarray(rcount, dtype=np.int64))
        out = []
        for s, size in enumerate(fview.sizes):
            fc, va, ac = fcodes[s, :size], fvalid[s, :size], acodes[s, :size]
            jc, jv = jcodes[s, :size], jvalid[s, :size]
            res = []
            for code_lo, code_hi in code_bounds:
                mask = (fc >= code_lo) & (fc < code_hi) & va
                counts = np.bincount(ac[mask], minlength=aview.dict_size)
                keep = mask & jv
                res.append((int(counts @ adict), int(mask.sum()),
                            int(rcount[jc[keep]].sum())))
            out.append(res)
        return out

    def encode_values_shards(self, encoder: Callable[[np.ndarray], np.ndarray],
                             values_list: Sequence[np.ndarray]
                             ) -> list[np.ndarray]:
        """Encode every island's pending update values through one shared
        value->code map. Reference: one encoder call per island; the
        accelerator backend batches all islands into one probe launch."""
        return [np.asarray(encoder(v)) for v in values_list]

    # -- update propagation (§5) ------------------------------------------
    @abc.abstractmethod
    def merge_update_logs(self, logs: Iterable[np.ndarray]) -> np.ndarray:
        """K-way merge of commit-ordered per-thread logs into the final log."""

    @abc.abstractmethod
    def sort_unique(self, values: np.ndarray) -> np.ndarray:
        """Sort + dedupe pending update values -> update dictionary."""

    @abc.abstractmethod
    def merge_dictionaries(self, old_dict: np.ndarray,
                           update_dict: np.ndarray) -> np.ndarray:
        """Linear merge of two sorted dictionaries -> sorted-unique union."""

    @abc.abstractmethod
    def make_encoder(self, dictionary: np.ndarray
                     ) -> Callable[[np.ndarray], np.ndarray]:
        """value -> code lookup for values present in `dictionary` (§5.2's
        hash index; also used for the old_code -> new_code re-encode map)."""

    def sort_unique_batch(self, values_list: Sequence[np.ndarray]
                          ) -> list[np.ndarray]:
        """`sort_unique` over several pending-update value sets (one per
        column of a ship batch). Reference: one sort per set; the
        accelerator backend rides every set as a row of ONE sorter
        dispatch. Results are elementwise identical either way."""
        return [self.sort_unique(v) for v in values_list]

    def merge_dictionaries_batch(self, pairs: Sequence[tuple[np.ndarray,
                                                             np.ndarray]]
                                 ) -> list[np.ndarray]:
        """`merge_dictionaries` over several (old, update) dictionary
        pairs. Reference: one merge per pair; the accelerator backend
        merges every pair as a row of ONE merge dispatch. Results are
        elementwise identical either way."""
        return [self.merge_dictionaries(o, u) for o, u in pairs]

    def staged_encoder(self, new_dict: np.ndarray
                       ) -> Callable[[np.ndarray], np.ndarray]:
        """value -> code map for a ship batch's STAGED writes. Every staged
        write value is a pending update value, so it is in update_dict ⊆
        new_dict by construction — a vectorized binary search over the
        merged dictionary is exact, with no hash-table build or probe
        dispatch (`make_encoder` stays the general-purpose encoder for
        values that may miss)."""
        d = np.asarray(new_dict)
        return lambda values: np.searchsorted(d, values).astype(np.int64)

    def apply_stages_batch(self, per_column: Sequence[tuple[np.ndarray,
                                                            np.ndarray]]
                           ) -> list[tuple]:
        """Stages 1-2 of the optimized update application for every column
        of a ship batch: per (old_dict, write_vals) pair, sort+dedupe the
        pending values into the update dictionary, linear-merge the sorted
        dictionaries, and derive the staged encoder + positional old->new
        code map (both dictionaries are sorted and every old value survives
        the merge, so each old entry's new code is its merged position).
        Returns [(update_dict, new_dict, encode, old_to_new)] in order.

        This default rides the batched sorter/merge dispatches;
        PallasBackend overrides it with ONE donated-buffer fused launch
        (sort + bitonic half-cleaner merge) per ship batch."""
        upd: list = [None] * len(per_column)
        nonempty = [i for i, (_, wv) in enumerate(per_column) if len(wv)]
        for i, u in zip(nonempty, self.sort_unique_batch(
                [per_column[i][1] for i in nonempty])):
            upd[i] = u
        for i in range(len(per_column)):
            if upd[i] is None:
                upd[i] = np.empty(0, np.int32)
        new_dicts = self.merge_dictionaries_batch(
            [(old, u) for (old, _), u in zip(per_column, upd)])
        return [(u, nd, self.staged_encoder(nd),
                 np.searchsorted(nd, old).astype(np.int64))
                for u, nd, (old, _) in zip(upd, new_dicts, per_column)]

    # -- consistency (§6) --------------------------------------------------
    @abc.abstractmethod
    def snapshot_column(self, col: EncodedColumn,
                        prev: EncodedColumn | None = None) -> EncodedColumn:
        """Copy-unit snapshot of `col`; `prev` is the chain head, from which
        clean chunks may be carried instead of re-read."""


def _side_counts(col: EncodedColumn, mask: np.ndarray | None
                 ) -> tuple[np.ndarray, np.ndarray]:
    """One join side's per-dictionary-value occurrence counts."""
    values = np.asarray(col.dictionary)
    keep = np.asarray(col.valid)
    if mask is not None:
        keep = np.asarray(mask) & keep
    codes = np.asarray(col.codes)[keep]
    return values, np.bincount(codes, minlength=len(values)).astype(np.int64)


def _join_counts(left: EncodedColumn, right: EncodedColumn,
                 left_mask: np.ndarray | None):
    """Shared join prep: per-dictionary-value occurrence counts."""
    lv, lcount = _side_counts(left, left_mask)
    rv, rcount = _side_counts(right, None)
    return lv, rv, lcount, rcount


def _fits_int32(values: np.ndarray) -> bool:
    if len(values) == 0:
        return True
    # dtype short-circuit: any integer dtype of <= 32 bits fits by
    # construction — skips the min/max scans on the hot ship path
    if values.dtype.kind in "iu" and values.dtype.itemsize <= (
            4 if values.dtype.kind == "i" else 2):
        return True
    info = np.iinfo(np.int32)
    return bool(values.min() >= info.min and values.max() <= info.max)


class NumpyBackend(ExecutionBackend):
    """The original pure-numpy hot path, extracted verbatim."""

    name = "numpy"

    def filter_mask(self, col, lo, hi):
        code_lo, code_hi = self.code_range(col, lo, hi)
        codes = np.asarray(col.codes)
        return (codes >= code_lo) & (codes < code_hi) & np.asarray(col.valid)

    def aggregate_sum(self, col, mask):
        """Histogram-of-codes aggregate: one sequential pass, no random access."""
        codes = np.asarray(col.codes)
        counts = np.bincount(codes[mask], minlength=col.dict_size)
        return int(counts @ np.asarray(col.dictionary, dtype=np.int64))

    def filter_agg(self, fcol, acol, lo, hi):
        mask = self.filter_mask(fcol, lo, hi)
        return self.aggregate_sum(acol, mask), int(mask.sum())

    def filter_agg_mask(self, fcol, acol, lo, hi):
        # one scan: the mask is the aggregate's by-product, as in the
        # original engine code path
        mask = self.filter_mask(fcol, lo, hi)
        return self.aggregate_sum(acol, mask), int(mask.sum()), mask

    def filter_agg_batch(self, fcol, acol, bounds):
        # one materialization of the encoded columns, shared by all queries
        fcodes = np.asarray(fcol.codes)
        fvalid = np.asarray(fcol.valid)
        acodes = np.asarray(acol.codes)
        adict = np.asarray(acol.dictionary, dtype=np.int64)
        fdict = np.asarray(fcol.dictionary)
        out = []
        for lo, hi in bounds:
            code_lo = np.searchsorted(fdict, lo, side="left")
            code_hi = np.searchsorted(fdict, hi, side="right")
            mask = (fcodes >= code_lo) & (fcodes < code_hi) & fvalid
            counts = np.bincount(acodes[mask], minlength=acol.dict_size)
            out.append((int(counts @ adict), int(mask.sum())))
        return out

    def _join_match(self, lv, rv, lcount, rcount):
        """Match pre-grouped dictionary counts (the join's build+probe)."""
        common, li, ri = np.intersect1d(lv, rv, return_indices=True)
        return int((lcount[li] * rcount[ri]).sum())

    def hash_join_count(self, left, right, left_mask=None):
        return self._join_match(*_join_counts(left, right, left_mask))

    def merge_update_logs(self, logs):
        logs = [l for l in logs if len(l)]
        if not logs:
            return np.empty(0, dtype=UPDATE_DTYPE)
        cat = np.concatenate(logs)
        order = np.argsort(cat["commit_id"], kind="stable")
        return cat[order]

    def sort_unique(self, values):
        return np.unique(values)

    def merge_dictionaries(self, old_dict, update_dict):
        return np.union1d(old_dict, update_dict).astype(old_dict.dtype)

    def make_encoder(self, dictionary):
        d = np.asarray(dictionary)
        return lambda values: np.searchsorted(d, values)

    def snapshot_column(self, col, prev=None):
        # JAX arrays are immutable: aliasing IS a consistent snapshot. The
        # hardware copy is priced by the caller regardless.
        return EncodedColumn(codes=col.codes, dictionary=col.dictionary,
                             valid=col.valid, version=col.version)


class PallasBackend(NumpyBackend):
    """Dispatches the hot path to the PIM-analog Pallas kernels.

    Inherits numpy glue (bincounts, grouping) — the paper's fixed-function
    units do the data-plane work while small control-plane steps stay on the
    host. Falls back to the numpy path only where a kernel precondition
    can't hold (e.g. sort/probe values beyond int32, EMPTY_KEY colliding
    with a dictionary value, a commit id equal to the int64 merge
    sentinel); every fallback keeps results identical. Full int64 commit
    ids are first-class in the merge unit ((hi, lo) int32 lanes).
    """

    name = "pallas"

    # -- analytical engine -------------------------------------------------
    def filter_agg(self, fcol, acol, lo, hi):
        code_lo, code_hi = self.code_range(fcol, lo, hi)
        s, c = scan_filter_agg(fcol.codes, acol.codes, fcol.valid,
                               acol.dictionary, code_lo, code_hi, exact=True)
        return int(s), int(c)

    def filter_agg_mask(self, fcol, acol, lo, hi):
        # the fused kernel does not materialize the mask; produce it with
        # one extra host pass (explicit override — inheriting would pick up
        # NumpyBackend's all-numpy scan and bypass the kernel entirely)
        s, c = self.filter_agg(fcol, acol, lo, hi)
        return s, c, self.filter_mask(fcol, lo, hi)

    def filter_agg_batch(self, fcol, acol, bounds):
        if len(bounds) == 1:
            [(lo, hi)] = bounds
            return [self.filter_agg(fcol, acol, lo, hi)]
        code_bounds = [self.code_range(fcol, lo, hi) for lo, hi in bounds]
        return scan_filter_agg_batch(fcol.codes, acol.codes, fcol.valid,
                                     acol.dictionary, code_bounds)

    def scan_view(self, fview, aview, code_bounds):
        # every island in ONE batched launch over the leading shard axis;
        # padded slots carry valid=0, the exact scan identity
        fview.require_fresh()
        aview.require_fresh()
        return scan_filter_agg_sharded(fview.codes, aview.codes, fview.valid,
                                       aview.dictionary, code_bounds)

    def filter_agg_join_batch(self, fcol, acol, jcol, bounds, rcount=None):
        # the whole join group in ONE fused device call: the self-join is a
        # second exact scan with the build side's occurrence histogram as
        # the dictionary (counts <= n_rows keep it int32-exact); the host
        # contributes only the build-side bincount, once per group.
        code_bounds = [self.code_range(fcol, lo, hi) for lo, hi in bounds]
        if rcount is None:
            rcount = np.bincount(
                np.asarray(jcol.codes)[np.asarray(jcol.valid)],
                minlength=jcol.dict_size)
        rcount = np.asarray(rcount).astype(np.int32)
        return scan_filter_agg_join(fcol.codes, acol.codes, jcol.codes,
                                    fcol.valid, jcol.valid, acol.dictionary,
                                    rcount, code_bounds)

    def scan_view_join(self, fview, aview, jview, code_bounds, rcount=None):
        # every island's join group in the same single launch; the build
        # side is the view's cached global histogram (dict_counts, or the
        # delta-corrected override), so the per-island partial join counts
        # sum exactly across shards
        fview.require_fresh()
        aview.require_fresh()
        jview.require_fresh()
        rcount = (jview.dict_counts() if rcount is None
                  else np.asarray(rcount)).astype(np.int32)
        return scan_filter_agg_join_sharded(
            fview.codes, aview.codes, jview.codes, fview.valid, jview.valid,
            aview.dictionary, rcount, code_bounds)

    def filter_agg_values_batch(self, fvals, avals, valid, bounds):
        # raw-value correction scan on the same split-accumulator machinery
        # (kernels/dict_ops.scan_values_agg) — the overlay is flat host
        # data, small relative to the base column, one launch per call
        return scan_values_agg(fvals, avals, valid, bounds)

    def filter_agg_values_delta(self, corr, bounds):
        # effective and base correction scans fused into ONE launch
        return scan_values_delta(corr, bounds)

    def filter_agg_delta_batch(self, fcol, acol, bounds, corr):
        # the whole delta-merged group — base multi-predicate scan plus
        # both overlay correction scans — as ONE traced launch, instead of
        # the base launch + two correction launches the composition costs
        if corr is None:
            return self.filter_agg_batch(fcol, acol, bounds)
        code_bounds = [self.code_range(fcol, lo, hi) for lo, hi in bounds]
        return scan_filter_agg_group(fcol.codes, acol.codes, fcol.valid,
                                     acol.dictionary, code_bounds, corr,
                                     bounds)

    def filter_agg_join_delta_batch(self, fcol, acol, jcol, bounds, rcount,
                                    corr_a, corr_j):
        # delta-merged join group in ONE fused launch: base aggregate +
        # join scans and all four correction scans share a single trace
        if corr_a is None and corr_j is None:
            return self.filter_agg_join_batch(fcol, acol, jcol, bounds,
                                              rcount=rcount)
        code_bounds = [self.code_range(fcol, lo, hi) for lo, hi in bounds]
        if rcount is None:
            rcount = np.bincount(
                np.asarray(jcol.codes)[np.asarray(jcol.valid)],
                minlength=jcol.dict_size)
        rcount = np.asarray(rcount).astype(np.int32)
        return scan_filter_agg_join_group(
            fcol.codes, acol.codes, jcol.codes, fcol.valid, jcol.valid,
            acol.dictionary, rcount, code_bounds, corr_a, corr_j, bounds)

    def _join_match(self, lv, rv, lcount, rcount):
        if (len(rv) == 0 or len(lv) == 0
                or (rv == int(EMPTY_KEY)).any()       # can't build the table
                or (lv == int(EMPTY_KEY)).any()):     # probe matches empties
            return super()._join_match(lv, rv, lcount, rcount)
        # hash unit: probe each left dictionary value against the right
        # dictionary's table; hits multiply pre-grouped occurrence counts.
        table = build_table(rv, np.arange(len(rv), dtype=np.int32))
        ri = probe(table, lv, default=-1)
        hit = ri >= 0
        return int((lcount[hit] * rcount[ri[hit]]).sum())

    # -- update propagation ------------------------------------------------
    def merge_update_logs(self, logs):
        logs = [l for l in logs if len(l)]
        if not logs:
            return np.empty(0, dtype=UPDATE_DTYPE)
        cat = np.concatenate(logs)
        if len(logs) == 1:
            return cat
        # full-width int64 commit ids: the comparator tree merges (hi, lo)
        # int32 lanes, so ids beyond 2^31 need no fallback path
        _, src = merge_sorted_runs([l["commit_id"] for l in logs])
        idx = np.asarray(src)
        return cat[idx[idx >= 0]]

    def sort_unique(self, values):
        if len(values) == 0 or not _fits_int32(np.asarray(values)):
            return super().sort_unique(values)  # int32 sort unit
        v = np.asarray(values, dtype=np.int32)
        if len(values) <= 1024:  # the paper's 1024-value sort unit
            s = np.asarray(sort_1024(v))
        else:
            s = np.asarray(sort_rows(v[None, :])[0])
        keep = np.concatenate([[True], s[1:] != s[:-1]])
        return s[keep].astype(np.asarray(values).dtype)

    def merge_dictionaries(self, old_dict, update_dict):
        if len(old_dict) == 0 or len(update_dict) == 0:
            return super().merge_dictionaries(old_dict, update_dict)
        _, src = merge_sorted_runs([old_dict, update_dict])
        idx = np.asarray(src)
        cat = np.concatenate([np.asarray(old_dict), np.asarray(update_dict)])
        merged = cat[idx[idx >= 0]]
        keep = np.concatenate([[True], merged[1:] != merged[:-1]])
        return merged[keep].astype(old_dict.dtype)

    def sort_unique_batch(self, values_list):
        """Every value set rides one row of a single sorter dispatch.

        Each row's sorted prefix is exactly that set's sorted multiset
        (the network is row-independent and sentinels fill the tails), so
        per-row dedup yields the same update dictionary as `sort_unique`.
        Sets the sort unit can't take (empty / beyond int32) fall back to
        the scalar path, as does a batch with fewer than two sortable sets.
        """
        vals = [np.asarray(v) for v in values_list]
        batchable = [i for i, v in enumerate(vals)
                     if len(v) and _fits_int32(v)]
        if len(batchable) < 2:
            return [self.sort_unique(v) for v in vals]
        width = max(len(vals[i]) for i in batchable)
        stack = np.full((len(batchable), width), np.iinfo(np.int32).max,
                        dtype=np.int32)
        for r, i in enumerate(batchable):
            stack[r, :len(vals[i])] = vals[i].astype(np.int32)
        rows = np.asarray(sort_rows(stack))
        out: list = [None] * len(vals)
        for r, i in enumerate(batchable):
            s = rows[r, :len(vals[i])]
            keep = np.concatenate([[True], s[1:] != s[:-1]])
            out[i] = s[keep].astype(vals[i].dtype)
        for i, v in enumerate(vals):
            if out[i] is None:
                out[i] = self.sort_unique(v)
        return out

    def merge_dictionaries_batch(self, pairs):
        """Every (old, update) pair rides one row of a single merge
        dispatch (`merge_sorted_pairs`); per-row dedup of the merged keys
        yields the same dictionary as `merge_dictionaries`. Pairs with an
        empty side keep the scalar path (numpy union), as does a batch
        with fewer than two mergeable pairs."""
        pairs = [(np.asarray(o), np.asarray(u)) for o, u in pairs]
        batchable = [i for i, (o, u) in enumerate(pairs)
                     if len(o) and len(u)]
        if len(batchable) < 2:
            return [self.merge_dictionaries(o, u) for o, u in pairs]
        merged_keys = merge_sorted_pairs([pairs[i][0] for i in batchable],
                                         [pairs[i][1] for i in batchable])
        out: list = [None] * len(pairs)
        for r, i in enumerate(batchable):
            m = merged_keys[r]
            keep = np.concatenate([[True], m[1:] != m[:-1]])
            out[i] = m[keep].astype(pairs[i][0].dtype)
        for i, (o, u) in enumerate(pairs):
            if out[i] is None:
                out[i] = self.merge_dictionaries(o, u)
        return out

    def apply_stages_batch(self, per_column):
        """The whole ship batch's dictionary stages as ONE donated-buffer
        fused launch (kernels/dict_ops.apply_pipeline_batch): every
        column's update values ride one row of a single sort network and
        merge with its old dictionary through the bitonic half-cleaner in
        the same trace — replacing the separate sorter and merge dispatches
        of the batched composition. The old-dictionary and value sides get
        independent `common.width_bucket` widths, so the sort network runs
        at the (usually small) value width instead of the dictionary
        width, and tiny 8/16/32-wide deltas get dedicated short networks.

        Columns the fused pipeline can't take — an empty side (nothing to
        sort or merge), values beyond int32, or values colliding with the
        int32.max sentinel pad — fall back to the compositional default,
        as does a batch with fewer than two fusable columns. A batch with
        an old dictionary larger than the merge networks take
        (`MERGE_NETWORK_MAX_DICT`) merges every column by insertion
        (`_insert_stages`): it never meets a network shape that a smaller
        dictionary has not compiled already. Results are elementwise
        identical either way."""
        if any(len(o) > MERGE_NETWORK_MAX_DICT for o, _ in per_column):
            return [self._insert_stages(np.asarray(o), np.asarray(wv))
                    for o, wv in per_column]

        cols = [(np.asarray(o), np.asarray(wv)) for o, wv in per_column]
        imax = np.iinfo(np.int32).max

        def fusable(o, wv):
            # old dictionaries are sorted, so o[-1] is the max
            return (len(o) > 0 and len(wv) > 0 and _fits_int32(o)
                    and _fits_int32(wv) and int(o[-1]) < imax
                    and int(wv.max()) < imax)

        fused = [i for i, (o, wv) in enumerate(cols) if fusable(o, wv)]
        if len(fused) < 2:
            return super().apply_stages_batch(per_column)
        w_old = width_bucket(max(len(cols[i][0]) for i in fused))
        w_val = width_bucket(max(len(cols[i][1]) for i in fused))
        old_stack = np.full((len(fused), w_old), imax, dtype=np.int32)
        val_stack = np.full((len(fused), w_val), imax, dtype=np.int32)
        for r, i in enumerate(fused):
            o, wv = cols[i]
            old_stack[r, :len(o)] = o.astype(np.int32)
            val_stack[r, :len(wv)] = wv.astype(np.int32)
        sorted_vals, merged = apply_pipeline_batch(old_stack, val_stack)
        sorted_vals = np.asarray(sorted_vals)
        merged = np.asarray(merged)
        out: list = [None] * len(cols)
        for r, i in enumerate(fused):
            o, wv = cols[i]
            s = sorted_vals[r, :len(wv)]
            u = s[np.concatenate([[True], s[1:] != s[:-1]])].astype(wv.dtype)
            m = merged[r, :len(o) + len(wv)]
            nd = m[np.concatenate([[True], m[1:] != m[:-1]])].astype(o.dtype)
            out[i] = (u, nd, self.staged_encoder(nd),
                      np.searchsorted(nd, o).astype(np.int64))
        rest = [i for i in range(len(cols)) if out[i] is None]
        if rest:
            for i, stage in zip(rest, super().apply_stages_batch(
                    [per_column[i] for i in rest])):
                out[i] = stage
        return out

    def _insert_stages(self, old_dict, write_vals):
        """Stages 1-2 of one column by insertion: the update dictionary is
        the values' sorted set, and the merged dictionary is the old one
        with the genuinely new values inserted at their thresholds
        (`dsm.new_values`), an O(k) copy on the host."""
        upd = np.unique(write_vals).astype(write_vals.dtype)
        t, new = new_values(old_dict, upd)
        nd = np.insert(old_dict, t, new.astype(old_dict.dtype))
        k = np.arange(len(old_dict))
        old_to_new = k + np.searchsorted(t, k, side="right")
        return (upd, nd, self.staged_encoder(nd), old_to_new.astype(np.int64))

    def reencode_resident(self, codes, valid, thresholds, write_rows,
                          write_codes, del_rows):
        """Stage 3 of the optimized apply on a device-resident column:
        the re-encode as a compare-and-add against the new values'
        thresholds, then the row ops, in one launch
        (kernels/reencode). Returns the new ``(codes, valid)`` device
        arrays; the inputs stay valid (no donation), so pinned snapshots
        that alias them keep reading their version."""
        return reencode_rows(codes, valid, thresholds, write_rows,
                             write_codes, del_rows)

    def make_encoder(self, dictionary):
        d = np.asarray(dictionary)
        if (len(d) == 0 or not _fits_int32(d)
                or (d == int(EMPTY_KEY)).any()):
            return super().make_encoder(dictionary)
        table = build_table(d, np.arange(len(d), dtype=np.int32))
        fallback = super().make_encoder(dictionary)

        def encode(values):
            values = np.asarray(values)
            if len(values) == 0:
                return np.empty(0, dtype=np.int64)
            if not _fits_int32(values):
                return fallback(values)  # int32 probe unit
            codes = probe(table, values.astype(np.int32))
            return codes.astype(np.int64)

        encode._table = table  # lets encode_values_shards batch the probes
        return encode

    def encode_values_shards(self, encoder, values_list):
        table = getattr(encoder, "_table", None)
        vals = [np.asarray(v) for v in values_list]
        if table is None or not all(_fits_int32(v) for v in vals):
            return super().encode_values_shards(encoder, vals)
        # one probe launch covers every island's update-value encodes
        codes = probe_sharded(table, [v.astype(np.int32) for v in vals])
        return [c.astype(np.int64) for c in codes]

    # -- consistency -------------------------------------------------------
    def snapshot_column(self, col, prev=None):
        n = col.n_rows
        if n == 0:
            return super().snapshot_column(col, prev)
        n_chunks = (n + SNAPSHOT_BLOCK - 1) // SNAPSHOT_BLOCK
        if (prev is not None and prev.n_rows == n
                and (prev.dictionary is col.dictionary  # snapshots alias
                     or np.array_equal(np.asarray(prev.dictionary),
                                       np.asarray(col.dictionary)))):
            # tracking buffer: only chunks that changed since the previous
            # snapshot are fetched from the main replica (codes are only
            # comparable when the dictionaries match). A device-resident
            # column computes it on the device.
            if isinstance(col.codes, jax.Array):
                dirty = dirty_chunks(col.codes, prev.codes,
                                     block=SNAPSHOT_BLOCK)
            else:
                diff = np.asarray(col.codes) != np.asarray(prev.codes)
                dirty = np.zeros(n_chunks, dtype=bool)
                full = n // SNAPSHOT_BLOCK
                if full:
                    dirty[:full] = diff[:full * SNAPSHOT_BLOCK].reshape(
                        full, SNAPSHOT_BLOCK).any(axis=1)
                if full < n_chunks:
                    dirty[full] = diff[full * SNAPSHOT_BLOCK:].any()
                dirty = dirty.astype(np.int32)
            prev_arr = prev.codes
        else:
            dirty = np.ones(n_chunks, dtype=np.int32)
            prev_arr = col.codes
        codes = snapshot_copy(col.codes, prev_arr, dirty,
                              block=SNAPSHOT_BLOCK)
        return EncodedColumn(codes=codes, dictionary=col.dictionary,
                             valid=col.valid, version=col.version)


# ---------------------------------------------------------------------------
# Sharded multi-replica analytical islands (§4, Fig. 5)
# ---------------------------------------------------------------------------

def reduce_partials(kind: str, parts: Sequence[int | None]) -> int | None:
    """Exact cross-shard reduction of split-accumulator partials.

    Partial aggregates arrive from each island as exact python ints (the
    kernels' split accumulators are reassembled per shard); the cross-shard
    reduce stays in plain arbitrary-precision int arithmetic so the final
    answer is bit-identical to the unsharded scan. ``None`` marks a partial
    from a shard with no qualifying rows (identity element for min/max).
    """
    live = [int(p) for p in parts if p is not None]
    if kind in ("sum", "count"):
        return sum(live)
    if kind == "min":
        return min(live) if live else None
    if kind == "max":
        return max(live) if live else None
    raise ValueError(f"unknown aggregate kind {kind!r}")


class ShardedBackend(ExecutionBackend):
    """Multiple analytical islands: N row-wise DSM shards over one inner backend.

    Polynesia scales analytics out by replicating the analytical island —
    each island owns a *resident* DSM shard plus a replicated dictionary
    (§4, Fig. 5). Residency is materialized as `dsm.ShardedView`: the
    engine shards each pinned snapshot column ONCE per query round
    (`shard_view`, normally driven by `ConsistencyManager.read_scan`) into
    stacked equal-shaped shard arrays, and every scan-family operator then
    executes all islands through the inner backend's `scan_view` — one
    batched kernel launch on the accelerator backend, a serial per-shard
    host loop kept only as the numpy reference. The exact partial
    (sum, count) pairs reduce with `reduce_partials`.

    Operators also accept raw EncodedColumns (an ad-hoc view is built on
    the fly — semantically the old re-shard-per-call path); a stale
    ShardedView is a hard `dsm.StaleShardedViewError`, never silently
    refreshed.

    Update-propagation operators (log merge, update-dictionary sort,
    dictionary merge, value encode) delegate to the inner backend: the
    dictionary is replicated, so those stages run once and every island
    re-encodes its shard through the same old->new map (see
    application.apply_updates_shards, which routes row ops to owning
    shards and batches all islands' value encodes into one probe launch).
    """

    def __init__(self, inner: str | ExecutionBackend, n_shards: int):
        if isinstance(inner, ShardedBackend):
            raise ValueError("cannot nest ShardedBackend inside ShardedBackend")
        inner = get_backend(inner, n_shards=1)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.inner = inner
        self.n_shards = int(n_shards)
        self.name = f"{inner.name}@{self.n_shards}"

    # -- the sharded snapshot plane ---------------------------------------
    def shard_view(self, col: EncodedColumn, snapshot_id: int = -1
                   ) -> ShardedView:
        """Materialize the islands' resident shards of `col` (shard once)."""
        return make_sharded_view(col, self.n_shards, snapshot_id=snapshot_id)

    def _as_view(self, col) -> ShardedView:
        if isinstance(col, ShardedView):
            col.require_fresh()
            if col.n_shards != self.n_shards:
                raise ValueError(
                    f"ShardedView has {col.n_shards} shards but backend "
                    f"{self.name!r} has {self.n_shards} islands")
            return col
        return self.shard_view(col)

    # -- analytical engine -------------------------------------------------
    def _mask2d(self, view: ShardedView, lo: int, hi: int) -> np.ndarray:
        code_lo, code_hi = self.code_range(view, lo, hi)
        codes = np.asarray(view.codes)
        return (codes >= code_lo) & (codes < code_hi) & np.asarray(view.valid)

    def filter_mask(self, col, lo, hi):
        view = self._as_view(col)
        m2d = self._mask2d(view, lo, hi)
        return np.concatenate([m2d[s, :size]
                               for s, size in enumerate(view.sizes)])

    def filter_agg(self, fcol, acol, lo, hi):
        [(total_s, total_c)] = self.filter_agg_batch(fcol, acol, [(lo, hi)])
        return total_s, total_c

    def filter_agg_mask(self, fcol, acol, lo, hi):
        fv, av = self._as_view(fcol), self._as_view(acol)
        [per_shard] = zip(*self.inner.scan_view(
            fv, av, [self.code_range(fv, lo, hi)]))
        m2d = self._mask2d(fv, lo, hi)
        mask = np.concatenate([m2d[s, :size]
                               for s, size in enumerate(fv.sizes)])
        return (reduce_partials("sum", [s for s, _ in per_shard]),
                reduce_partials("count", [c for _, c in per_shard]), mask)

    def filter_agg_batch(self, fcol, acol, bounds):
        fv, av = self._as_view(fcol), self._as_view(acol)
        code_bounds = [self.code_range(fv, lo, hi) for lo, hi in bounds]
        per_shard = self.inner.scan_view(fv, av, code_bounds)
        return [(reduce_partials("sum", [p[q][0] for p in per_shard]),
                 reduce_partials("count", [p[q][1] for p in per_shard]))
                for q in range(len(bounds))]

    def filter_agg_join_batch(self, fcol, acol, jcol, bounds, rcount=None):
        # one scan_view_join covers every island's aggregate AND join scans;
        # the per-island (sum, count, join) partials all reduce as exact sums
        fv, av, jv = self._as_view(fcol), self._as_view(acol), \
            self._as_view(jcol)
        code_bounds = [self.code_range(fv, lo, hi) for lo, hi in bounds]
        per_shard = self.inner.scan_view_join(fv, av, jv, code_bounds,
                                              rcount=rcount)
        return [(reduce_partials("sum", [p[q][0] for p in per_shard]),
                 reduce_partials("count", [p[q][1] for p in per_shard]),
                 reduce_partials("sum", [p[q][2] for p in per_shard]))
                for q in range(len(bounds))]

    def filter_agg_values_batch(self, fvals, avals, valid, bounds):
        # the correction scan runs over the flat overlay union, which is not
        # row-partitioned across islands (overlays are tiny relative to the
        # base shards) — delegate to the inner backend's single launch
        return self.inner.filter_agg_values_batch(fvals, avals, valid, bounds)

    def filter_agg_values_delta(self, corr, bounds):
        # flat overlay stack, same residency argument as above
        return self.inner.filter_agg_values_delta(corr, bounds)

    def filter_agg_delta_batch(self, fcol, acol, bounds, corr):
        # on the accelerator inner backend the whole delta-merged group —
        # every island's base scan over its resident shard AND the flat
        # overlay correction scans — rides ONE fused launch; other inners
        # keep the compositional default (sharded base + inner correction)
        if corr is None:
            return self.filter_agg_batch(fcol, acol, bounds)
        if not isinstance(self.inner, PallasBackend):
            return super().filter_agg_delta_batch(fcol, acol, bounds, corr)
        fv, av = self._as_view(fcol), self._as_view(acol)
        code_bounds = [self.code_range(fv, lo, hi) for lo, hi in bounds]
        return scan_filter_agg_group_sharded(fv.codes, av.codes, fv.valid,
                                             av.dictionary, code_bounds,
                                             corr, bounds)

    def hash_join_count(self, left, right, left_mask=None):
        # Each island histograms only its own resident probe-side shard;
        # the partial histograms reduce exactly in int arithmetic. The
        # build side (the replicated right dictionary's counts) depends
        # only on the pinned data, so it lives on the view
        # (`ShardedView.dict_counts`): built once, reused by every join
        # group probing the same pinned snapshot, and invalidated with the
        # view at the Phase-2 swap. The match runs once on the inner
        # backend (hash unit on PallasBackend).
        lview = self._as_view(left)
        lv = np.asarray(lview.dictionary)
        lcount = self._view_side_counts(lview, left_mask)
        if right is left:  # the engine's self-join fast path
            rv, rcount = lv, lview.dict_counts()
        elif isinstance(right, ShardedView):
            rv = np.asarray(right.dictionary)
            rcount = right.dict_counts()
        else:
            rv, rcount = _side_counts(right, None)
        return self.inner._join_match(lv, rv, lcount, rcount)

    @staticmethod
    def _view_side_counts(view: ShardedView, mask) -> np.ndarray:
        """Per-dictionary-value occurrence counts, reduced across islands'
        resident shards — straight off the stacked arrays, no reassembly.
        The unmasked histogram has exactly one implementation: the view's
        cached build side (`ShardedView.dict_counts`)."""
        if mask is None:
            return view.dict_counts()
        codes = np.asarray(view.codes)
        keep2d = np.asarray(view.valid).copy()
        m = np.asarray(mask)
        for s, (lo, hi) in enumerate(zip(view.bounds, view.bounds[1:])):
            keep2d[s, :hi - lo] &= m[lo:hi]
        count = np.zeros(view.dict_size, dtype=np.int64)
        for s in range(view.n_shards):
            count += np.bincount(codes[s][keep2d[s]], minlength=view.dict_size
                                 ).astype(np.int64)
        return count

    # -- update propagation: dictionary stages run once (replicated dict) --
    def merge_update_logs(self, logs):
        return self.inner.merge_update_logs(logs)

    def sort_unique(self, values):
        return self.inner.sort_unique(values)

    def merge_dictionaries(self, old_dict, update_dict):
        return self.inner.merge_dictionaries(old_dict, update_dict)

    def sort_unique_batch(self, values_list):
        return self.inner.sort_unique_batch(values_list)

    def merge_dictionaries_batch(self, pairs):
        return self.inner.merge_dictionaries_batch(pairs)

    def staged_encoder(self, new_dict):
        return self.inner.staged_encoder(new_dict)

    def apply_stages_batch(self, per_column):
        # the dictionary is replicated, so the ship batch's fused
        # dictionary pipeline runs once on the inner backend
        return self.inner.apply_stages_batch(per_column)

    def make_encoder(self, dictionary):
        return self.inner.make_encoder(dictionary)

    def encode_values_shards(self, encoder, values_list):
        return self.inner.encode_values_shards(encoder, values_list)

    # -- consistency -------------------------------------------------------
    def snapshot_column(self, col, prev=None):
        # One stacked copy pass over the whole column: the per-island copy
        # units are modeled in hwmodel (island-scaled copy rate), and the
        # copy unit's chunk carry logic is position-based, so the result —
        # and, unlike the old per-shard loop, the launch count — matches
        # the unsharded backend exactly.
        return self.inner.snapshot_column(col, prev=prev)


class MeshBackend(ShardedBackend):
    """N analytical islands, each on its OWN device of a 1-D jax mesh.

    The mesh placement tier (spec ``"pallas@4/mesh"``): where
    `ShardedBackend` stacks every island's resident shard on one device
    and batches the launch over the leading axis, this backend lays the
    same stacked ``(n_shards, width)`` arrays across the devices of a
    `jax.Mesh` over ``distributed.ISLAND_AXIS`` — island *s*'s shard is
    *resident on device s*, exactly the paper's physically separate
    analytical islands (§4, Fig. 5). Residency is established once per
    pinned view (`shard_view` -> `distributed.place_shard_arrays`) or,
    on the Phase-2 swap path, directly from the per-island update
    application outputs (`place_shards` — per-device installs, no
    concat + re-split round trip; see `ConsistencyManager`).

    Execution is still O(1) kernel launches in the island count: the
    scan-family operators dispatch ONE ``shard_map`` call in which every
    device runs the same batched kernels over its local shard, and the
    cross-island reduction of the exact split-accumulator partials runs
    ON the mesh as an integer ``psum``
    (`kernels.dict_ops.scan_filter_agg_mesh` /
    `kernels.hash_probe.scan_filter_agg_join_mesh`) — replacing the host
    `reduce_partials` loop while staying bit-identical to it (16-bit
    psum lanes, recombined exactly on the host).

    On the eager plane the replica's columns are themselves resident
    views (`HTAPSession._place_replica`): stage 3 runs on every island's
    device in one launch (`reencode_islands`), and snapshots copy each
    shard on its device (`snapshot_column`). The dictionary stages run
    once on the host, as the dictionary is replicated.

    Requires `n_shards` devices; `distributed.island_mesh` raises an
    actionable error (naming the ``--xla_force_host_platform_device_count``
    CPU emulation escape hatch and the stacked fallback) when the process
    has fewer.
    """

    placement = "mesh"

    def __init__(self, inner: str | ExecutionBackend, n_shards: int):
        super().__init__(inner, n_shards)
        if not isinstance(self.inner, PallasBackend):
            raise ValueError(
                f"mesh placement runs the scan plane on the device mesh, "
                f"which the {self.inner.name!r} backend does not drive; "
                f"use 'pallas@{self.n_shards}/mesh', or keep "
                f"{self.inner.name!r} islands on the stacked placement "
                f"(e.g. '{self.inner.name}@{self.n_shards}')")
        self.mesh = island_mesh(self.n_shards)
        self.name = f"{self.inner.name}@{self.n_shards}/mesh"

    # -- the mesh-resident snapshot plane ----------------------------------
    def _place_view(self, view: ShardedView) -> ShardedView:
        view.codes, view.valid = place_shard_arrays(self.mesh, view.codes,
                                                    view.valid)
        return view

    def shard_view(self, col: EncodedColumn, snapshot_id: int = -1
                   ) -> ShardedView:
        """Shard once, then lay each island's shard on its own device."""
        return self._place_view(
            make_sharded_view(col, self.n_shards, snapshot_id=snapshot_id))

    def place_shards(self, shard_cols: Sequence[EncodedColumn],
                     snapshot_id: int = -1) -> ShardedView:
        """Phase-2 residency install: adopt the update application's
        per-island columns as a device-resident view directly — each
        island's freshly applied shard is device_put to its own device,
        with no concat + re-split round trip through the host."""
        return self._place_view(
            stack_shard_columns(shard_cols, snapshot_id=snapshot_id))

    # -- islands resident on their devices: stage 3 and snapshots there ---
    def reencode_islands(self, view: ShardedView, thresholds, writes,
                         del_rows):
        """Stage 3 of the optimized apply on every island's device, in one
        launch: the compare-and-add re-encode of each resident shard
        against the replicated ``thresholds``, then that island's row ops
        (``writes``: its ``(rows, codes)`` last writes; ``del_rows``: its
        deletes; both in local row ids). Returns the new stacked
        ``(codes, valid)``, each shard on its island's device; the inputs
        stay valid, so pinned snapshots that alias them keep their
        version."""
        return reencode_rows_mesh(view.codes, view.valid, thresholds,
                                  writes, del_rows, self.mesh)

    def snapshot_column(self, col, prev=None):
        """A resident island column (a `ShardedView`) is snapshotted on
        each island's device (`snapshot_copy_mesh`), carrying the chunks
        that match ``prev``'s shards under the same dictionary; a host
        column goes through the inner backend's copy unit."""
        if not isinstance(col, ShardedView):
            return super().snapshot_column(col, prev=prev)
        carry = (isinstance(prev, ShardedView) and prev.bounds == col.bounds
                 and (prev.dictionary is col.dictionary
                      or np.array_equal(np.asarray(prev.dictionary),
                                        np.asarray(col.dictionary))))
        codes = snapshot_copy_mesh(col.codes,
                                   prev.codes if carry else col.codes,
                                   self.mesh, block=SNAPSHOT_BLOCK)
        return ShardedView(codes=codes, valid=col.valid,
                           dictionary=col.dictionary, bounds=col.bounds,
                           version=col.version)

    # -- analytical engine: one shard_map launch, psum reduction -----------
    def filter_agg_batch(self, fcol, acol, bounds):
        fv, av = self._as_view(fcol), self._as_view(acol)
        code_bounds = [self.code_range(fv, lo, hi) for lo, hi in bounds]
        return scan_filter_agg_mesh(fv.codes, av.codes, fv.valid,
                                    av.dictionary, code_bounds, self.mesh)

    def filter_agg_mask(self, fcol, acol, lo, hi):
        fv, av = self._as_view(fcol), self._as_view(acol)
        [(s, c)] = scan_filter_agg_mesh(fv.codes, av.codes, fv.valid,
                                        av.dictionary,
                                        [self.code_range(fv, lo, hi)],
                                        self.mesh)
        m2d = self._mask2d(fv, lo, hi)
        mask = np.concatenate([m2d[i, :size]
                               for i, size in enumerate(fv.sizes)])
        return s, c, mask

    def filter_agg_join_batch(self, fcol, acol, jcol, bounds, rcount=None):
        # the whole join group in the same single shard_map launch; the
        # build side stays the view's cached GLOBAL histogram (replicated
        # to every island, like the dictionary, or the delta-corrected
        # override), so the on-mesh psum of the per-island partial join
        # counts is the exact total
        fv, av, jv = self._as_view(fcol), self._as_view(acol), \
            self._as_view(jcol)
        code_bounds = [self.code_range(fv, lo, hi) for lo, hi in bounds]
        rcount = (jv.dict_counts() if rcount is None
                  else np.asarray(rcount)).astype(np.int32)
        return scan_filter_agg_join_mesh(fv.codes, av.codes, jv.codes,
                                         fv.valid, jv.valid, av.dictionary,
                                         rcount, code_bounds, self.mesh)

    def filter_agg_delta_batch(self, fcol, acol, bounds, corr):
        # the resident shards live on the device mesh, so the base scan
        # must stay on the mesh entry point (the stacked fused group kernel
        # would pull every shard back to one device); the flat overlay
        # correction folds in from the inner backend's single fused launch
        fused = self.filter_agg_batch(fcol, acol, bounds)
        if corr is None:
            return fused
        deltas = self.inner.filter_agg_values_delta(corr, bounds)
        return [(s + ds, c + dc)
                for (s, c), (ds, dc) in zip(fused, deltas)]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

BACKENDS: dict[str, ExecutionBackend] = {
    "numpy": NumpyBackend(),
    "pallas": PallasBackend(),
}

_default_backend = os.environ.get("REPRO_BACKEND", "numpy")


def _shards_from_env() -> int:
    raw = os.environ.get("REPRO_SHARDS", "1")
    try:
        n = int(raw)
    except ValueError:
        raise ValueError(
            f"REPRO_SHARDS must be an integer >= 1, got {raw!r} "
            "(set e.g. REPRO_SHARDS=4, or pass n_shards=/--shards= "
            "instead)") from None
    if n < 1:
        raise ValueError(f"REPRO_SHARDS must be an integer >= 1, got {raw!r}")
    return n


# Island placements a spec may name: "stacked" keeps every island's shard
# on one device (leading-axis batched launches), "mesh" lays one island per
# device of a jax.Mesh (MeshBackend).
PLACEMENTS = ("stacked", "mesh")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """Structured backend selection: ``name[@N][/placement]``, parsed.

    The canonical form of every backend argument the drivers accept
    (``--backend``, ``SystemSpec.backend``, ``REPRO_BACKEND``):
    ``name`` is a registry key, ``n_shards`` the analytical-island count
    (None defers to the session default / ``REPRO_SHARDS``), ``placement``
    how islands are laid out (None defers to the session default /
    ``REPRO_PLACEMENT``, normally "stacked"). Frozen and validated at
    construction; `parse_backend_spec` builds one from the string grammar
    and ``str()`` round-trips back to it.
    """

    name: str
    n_shards: int | None = None
    placement: str | None = None

    def __post_init__(self):
        if not self.name or not isinstance(self.name, str):
            raise ValueError(
                f"BackendSpec needs a non-empty backend name, got "
                f"{self.name!r} (have {sorted(BACKENDS)})")
        if self.n_shards is not None and int(self.n_shards) < 1:
            raise ValueError(
                f"n_shards must be >= 1, got {self.n_shards} "
                f"(BackendSpec for {self.name!r})")
        if self.placement is not None and self.placement not in PLACEMENTS:
            raise ValueError(
                f"bad placement {self.placement!r} (BackendSpec for "
                f"{self.name!r}); expected one of {list(PLACEMENTS)}")

    def __str__(self) -> str:
        s = self.name
        if self.n_shards is not None:
            s += f"@{self.n_shards}"
        if self.placement is not None:
            s += f"/{self.placement}"
        return s


def parse_backend_spec(spec: str | BackendSpec) -> BackendSpec:
    """Validate a ``"name[@N][/placement]"`` backend spec early.

    Returns a `BackendSpec` (instances pass through). Malformed specs fail
    here with actionable messages — an empty name (``"@4"``), an empty or
    non-integer count (``"pallas@"``, ``"numpy@one"``) and an unknown or
    empty placement (``"pallas@4/ring"``, ``"pallas@4/"``) raise KeyError
    naming the expected form, and a non-positive count (``"pallas@0"``)
    raises ValueError — instead of surfacing as deep lookup errors.
    """
    if isinstance(spec, BackendSpec):
        return spec
    if not isinstance(spec, str) or not spec:
        raise KeyError(
            f"empty backend spec {spec!r}; expected 'name', 'name@N' or "
            f"'name@N/placement' with name in {sorted(BACKENDS)}, N >= 1 "
            f"and placement in {list(PLACEMENTS)}")
    base, psep, placement = spec.partition("/")
    if psep and placement not in PLACEMENTS:
        raise KeyError(
            f"bad placement {placement!r} in backend spec {spec!r}: "
            f"expected one of {list(PLACEMENTS)} (e.g. 'pallas@4/mesh')")
    name, sep, count = base.partition("@")
    if not name:
        raise KeyError(
            f"backend spec {spec!r} has an empty backend name; expected "
            f"'name', 'name@N' or 'name@N/placement' with name in "
            f"{sorted(BACKENDS)}")
    if not sep:
        return BackendSpec(name, None, placement if psep else None)
    try:
        n = int(count)
    except ValueError:
        raise KeyError(
            f"bad shard count {count!r} in backend spec {spec!r}: expected "
            "a decimal integer >= 1 (e.g. 'pallas@4')") from None
    if n < 1:
        raise ValueError(
            f"n_shards must be >= 1, got {n} (backend spec {spec!r})")
    return BackendSpec(name, n, placement if psep else None)


# Resolved lazily (like REPRO_BACKEND) so a bad REPRO_SHARDS value errors at
# first backend resolution, not at import, and --shards/set_default_n_shards
# can override it before it is ever read.
_default_n_shards: int | None = None
_default_placement: str | None = None


def _placement_from_env() -> str:
    raw = os.environ.get("REPRO_PLACEMENT", "stacked")
    if raw not in PLACEMENTS:
        raise ValueError(
            f"REPRO_PLACEMENT must be one of {list(PLACEMENTS)}, got {raw!r} "
            "(set e.g. REPRO_PLACEMENT=mesh, or pass a placement spec like "
            "'pallas@4/mesh' instead)")
    return raw


def register_backend(name: str, backend: ExecutionBackend) -> None:
    BACKENDS[name] = backend


def set_default_backend(name: str) -> None:
    """Set the backend used when callers pass backend=None (see also the
    REPRO_BACKEND environment variable). Accepts counted specs like
    ``"pallas@4"`` — the same forms get_backend resolves."""
    global _default_backend
    get_backend(name, n_shards=None)  # validates the name and any @N count
    _default_backend = name


def default_backend_name() -> str:
    return _default_backend


def set_default_n_shards(n: int) -> None:
    """Set the analytical-island (shard) count applied when callers resolve
    a backend by name/None without an explicit n_shards (see also the
    REPRO_SHARDS environment variable)."""
    global _default_n_shards
    if int(n) < 1:
        raise ValueError(f"n_shards must be >= 1, got {n}")
    _default_n_shards = int(n)


def default_n_shards() -> int:
    global _default_n_shards
    if _default_n_shards is None:
        _default_n_shards = _shards_from_env()
    return _default_n_shards


def set_default_placement(placement: str) -> None:
    """Set the island placement applied when callers resolve a backend
    without an explicit placement (see also the REPRO_PLACEMENT
    environment variable)."""
    global _default_placement
    if placement not in PLACEMENTS:
        raise ValueError(
            f"bad placement {placement!r}; expected one of "
            f"{list(PLACEMENTS)}")
    _default_placement = placement


def default_placement() -> str:
    global _default_placement
    if _default_placement is None:
        _default_placement = _placement_from_env()
    return _default_placement


def get_backend(spec: str | BackendSpec | ExecutionBackend | None = None,
                n_shards: int | None = None,
                placement: str | None = None) -> ExecutionBackend:
    """Resolve a backend argument: None -> session default, str -> registry.

    ``n_shards`` > 1 wraps the resolved backend in a `ShardedBackend`
    (None defers to the session default, normally 1), and
    ``placement="mesh"`` lays those islands one per device of a jax mesh
    (`MeshBackend`; None defers to the session default, normally
    "stacked"). Specs may carry both: ``"name@N/placement"``
    (e.g. ``"pallas@4/mesh"``), as a string or a `BackendSpec`. Passing a
    counted/placed spec alongside a contradicting explicit ``n_shards`` /
    ``placement`` raises. Already-constructed backend instances pass
    through untouched — they are never re-wrapped, and an explicit
    ``n_shards`` or ``placement`` that contradicts the instance raises
    rather than being silently dropped.
    """
    if isinstance(spec, ExecutionBackend):
        have = getattr(spec, "n_shards", 1)
        if n_shards is not None and int(n_shards) != have:
            raise ValueError(
                f"backend instance {getattr(spec, 'name', spec)!r} has "
                f"{have} shard(s) but n_shards={n_shards} was requested; "
                "pass the spec by name (e.g. 'pallas') to let n_shards "
                "wrap it")
        if placement is not None and placement != spec.placement:
            raise ValueError(
                f"backend instance {getattr(spec, 'name', spec)!r} uses "
                f"the {spec.placement!r} placement but "
                f"placement={placement!r} was requested; pass the spec by "
                f"name (e.g. 'pallas@{have}/{placement}') to let "
                "placement wrap it")
        return spec
    from_default = spec is None
    if from_default:
        spec = _default_backend
    parsed = parse_backend_spec(spec)
    name = parsed.name
    if parsed.n_shards is not None:
        if n_shards is None:
            n_shards = parsed.n_shards
        elif not from_default and int(n_shards) != parsed.n_shards:
            # a conflict is only meaningful when the caller passed the
            # counted spec itself; an explicit n_shards always overrides
            # the session default (e.g. fig10 sweeping shard counts while
            # REPRO_BACKEND=pallas@4 is set)
            raise ValueError(
                f"backend spec {name!r}@{parsed.n_shards} contradicts "
                f"n_shards={n_shards}")
    if parsed.placement is not None:
        if placement is None:
            placement = parsed.placement
        elif not from_default and placement != parsed.placement:
            raise ValueError(
                f"backend spec {str(parsed)!r} contradicts "
                f"placement={placement!r}")
    try:
        inner = BACKENDS[name]
    except KeyError:
        hint = (" (check the REPRO_BACKEND environment variable)"
                if from_default else "")
        raise KeyError(
            f"unknown backend {name!r}; have {sorted(BACKENDS)}{hint}"
        ) from None
    if n_shards is None:
        n_shards = default_n_shards()
    n_shards = int(n_shards)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards} "
                         f"(backend spec/argument for {name!r})")
    if placement is None:
        placement = default_placement()
    if placement not in PLACEMENTS:
        raise ValueError(
            f"bad placement {placement!r} (backend spec/argument for "
            f"{name!r}); expected one of {list(PLACEMENTS)}")
    if placement == "mesh":
        # a 1-island mesh is legal (one device) — the launch still runs
        # through shard_map, so placement semantics don't silently change
        # with the island count
        return _wrapped(inner, n_shards, "mesh")
    if n_shards > 1:
        return _wrapped(inner, n_shards, "stacked")
    return inner


# Wrapper backends are stateless (inner + shard count + mesh handle), so
# equal resolutions share one instance — get_backend("pallas@4/mesh") is
# get_backend("pallas@4/mesh"), matching the bare-name singletons. Keyed
# by the inner's identity so register_backend replacements miss the cache.
_wrapped_cache: dict[tuple[int, int, str], ExecutionBackend] = {}


def _wrapped(inner: ExecutionBackend, n_shards: int,
             placement: str) -> ExecutionBackend:
    key = (id(inner), n_shards, placement)
    be = _wrapped_cache.get(key)
    if be is None:
        cls = MeshBackend if placement == "mesh" else ShardedBackend
        be = cls(inner, n_shards)
        _wrapped_cache[key] = be
    return be
