"""Session-based HTAP API: `SystemSpec` presets + incremental `HTAPSession`.

Polynesia's contract (§4-§6) is an *open* system — transactions stream into
the txn island while update propagation, consistency and analytics proceed
concurrently. This module is that contract as an API:

* `SystemSpec` — one frozen config object naming a system composition
  (placement flags, hardware parameters, execution backend, island count,
  timing model). The eight named presets reproduce the paper's six systems
  and two normalization baselines:

      SystemSpec.polynesia()   SystemSpec.pim_only()
      SystemSpec.mi_sw()       SystemSpec.si_ss()
      SystemSpec.mi_sw_hb()    SystemSpec.si_mvcc()
      SystemSpec.ideal_txn()   SystemSpec.ana_only()

* `HTAPSession` — the long-lived incremental surface over one spec:

      session = HTAPSession(SystemSpec.polynesia(), table)
      session.execute(txn_chunk)        # any contiguous commit-order chunk
      answers = session.query_batch(qs) # fused-group + ShardedView path
      a = session.query(q)              # single query
      session.advance_round()           # explicit round boundary
      result = session.finish()         # -> htap.RunResult

The batch drivers in core/htap.py are thin wrappers that split a workload
into uniform rounds and drive a session — their answers are bit-identical
to the pre-session drivers (tests/golden_answers.json) across backends x
shards x timings. The session guarantees more: answers depend only on the
*visibility points* (which updates executed before each query), so any
sub-chunking of the txn stream between two query batches is answer- and
cost-neutral (tests/test_session.py's hypothesis sweep), which is what
lets arrival-process drivers (examples/htap_serve.py) interleave clients
mid-round — something the closed batch API could not express.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Callable

import jax
import numpy as np

from repro.core import engine
from repro.core.application import (apply_updates_delta,
                                    apply_updates_naive,
                                    apply_updates_shards,
                                    apply_updates_where, compaction_entries,
                                    delta_eligible, precompute_apply_stages)
from repro.core.backend import (ExecutionBackend, MeshBackend,
                                PallasBackend, get_backend)
from repro.core.consistency import ConsistencyManager
from repro.core.dsm import (ColumnDelta, DSMReplica, EncodedColumn,
                            ShardedView, empty_delta, host_column)
from repro.core.hwmodel import (CostLog, HardwareParams, HB_PARAMS,
                                HMC_PARAMS)
from repro.core.mvcc import MVCCStore
from repro.core.nsm import RowStore
from repro.core.placement import hybrid
from repro.core.schema import UpdateStream
from repro.core.shipping import ship_updates, FINAL_LOG_CAPACITY
from repro.core.snapshot import SnapshotStore
from repro.core.timeline import resolve_timing

# PIM-Only calibration: OLTP on in-order PIM cores pays extra cycles (no OoO
# ILP for pointer-heavy txn code) even though more threads are available.
PIM_TXN_CYCLE_FACTOR = 1.4


class SessionClosedError(RuntimeError):
    """The session was closed (`finish()` or `abort()`): no more traffic.

    Raised by every post-close surface — ``execute``, ``query``,
    ``query_batch``, ``advance_round``, ``flush_updates``, a second
    ``finish()``, ``checkpoint`` and ``resize_islands``. Subclasses
    RuntimeError so existing guards keep working.
    """

# Delta-store compaction trigger: raw overlay entries appended to a column
# before a background compaction folds the overlay into the base (§5.3's
# capacity-triggered maintenance shape; the overlay stays small enough that
# query-time base+overlay merges remain cheap).
DELTA_CAPACITY_DEFAULT = 4096
# `HTAPSession.counters` entries that `finish` reports on the MI family,
# and the stats key each goes under there.
FINISH_STATS = {"applications": "applications",
                "snapshots_created": "snapshots",
                "snapshots_shared": "shared",
                "views_built": "sharded_views",
                "views_shared": "views_shared",
                "views_resident": "views_resident"}


def _resolve_delta(spec: "SystemSpec") -> tuple[bool, int]:
    """(enabled, capacity) for a spec, with env fallbacks.

    ``delta_store=None`` defers to REPRO_DELTA (session default, like the
    backend/shards/timing env knobs); the env knob is silently ignored for
    non-MI kinds — only an *explicit* ``delta_store=True`` on those raises
    (in ``SystemSpec.__post_init__``), so a REPRO_DELTA=1 tier-1 run can
    still drive the single-instance baselines.
    """
    if spec.kind != "multi_instance":
        return False, DELTA_CAPACITY_DEFAULT
    enabled = spec.delta_store
    if enabled is None:
        enabled = os.environ.get("REPRO_DELTA", "") not in ("", "0")
    cap = spec.delta_capacity
    if cap is None:
        cap = int(os.environ.get("REPRO_DELTA_CAPACITY",
                                 DELTA_CAPACITY_DEFAULT))
    return bool(enabled), int(cap)

# System compositions a spec can name. "multi_instance" covers the MI
# family (MI+SW / MI+SW+HB / PIM-Only / Polynesia — the placement flags
# select which); the others are the single-instance and normalization
# baselines, each with its own storage engine and round semantics.
KINDS = ("multi_instance", "si_ss", "si_mvcc", "ideal_txn", "ana_only")


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    """A complete, immutable HTAP system configuration.

    Replaces the per-driver flag soup: every run is `(spec, workload)`.
    Presets return ready specs; keyword overrides refine them, e.g.
    ``SystemSpec.polynesia(backend="pallas", n_shards=4,
    timing="timeline", async_propagation=True)``.

    ``backend``/``n_shards``/``placement``/``timing`` of ``None`` defer to
    the session defaults (REPRO_BACKEND / REPRO_SHARDS / REPRO_PLACEMENT /
    REPRO_TIMING), exactly like the old driver kwargs. ``placement``
    selects how analytical islands are laid out: ``"stacked"`` batches
    every island on one device, ``"mesh"`` lays one island per device of a
    jax mesh (see `core.backend.MeshBackend`); backend specs may carry it
    inline (``backend="pallas@4/mesh"``).

    ``delta_store`` (MI family only) switches Phase 2 of update
    propagation from the eager two-stage column rebuild to the delta
    overlay plane: batches append to per-column sorted overlays, scans
    merge base+overlay, and a background compaction folds overlays into
    the base every ``delta_capacity`` appended entries. Answers are
    bit-identical to the eager path; ``None`` defers to REPRO_DELTA /
    REPRO_DELTA_CAPACITY.
    """

    name: str
    kind: str
    hw: HardwareParams = HMC_PARAMS
    # -- placement flags (multi_instance family) --------------------------
    propagation_on_pim: bool = False
    analytics_on_pim: bool = False
    txn_on_pim: bool = False
    optimized_application: bool = True
    # -- ablation / normalization switches --------------------------------
    shipping_only: bool = False          # zero-cost application (Fig. 2)
    zero_cost_propagation: bool = False  # Fig. 2/7 "Ideal" baseline
    zero_cost_snapshot: bool = False     # SI-SS normalization (Fig. 1/8)
    zero_cost_mvcc: bool = False         # SI-MVCC normalization (Fig. 1/8)
    # -- execution substrate ----------------------------------------------
    backend: str | ExecutionBackend | None = None
    n_shards: int | None = None
    placement: str | None = None
    timing: str | None = None
    async_propagation: bool = False
    # -- delta-store update plane (multi_instance family) ------------------
    # None defers to REPRO_DELTA / REPRO_DELTA_CAPACITY (session defaults)
    delta_store: bool | None = None
    delta_capacity: int | None = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown system kind {self.kind!r}; "
                             f"have {KINDS}")
        if self.delta_store and self.kind != "multi_instance":
            raise ValueError(
                f"delta_store is a multiple-instance mechanism (there is "
                f"no DSM replica to overlay); kind {self.kind!r} cannot "
                f"enable it")
        if self.delta_capacity is not None and self.delta_capacity <= 0:
            raise ValueError("delta_capacity must be a positive entry count")

    def replace(self, **overrides) -> "SystemSpec":
        """A copy with fields overridden (specs are frozen)."""
        return dataclasses.replace(self, **overrides)

    # -- the eight named presets ------------------------------------------
    @classmethod
    def polynesia(cls, **kw) -> "SystemSpec":
        """Full system: islands + in-memory accelerators (§4-§7)."""
        return cls(name="Polynesia", kind="multi_instance",
                   propagation_on_pim=True, analytics_on_pim=True
                   ).replace(**kw)

    @classmethod
    def mi_sw(cls, **kw) -> "SystemSpec":
        """Multiple instance, Polynesia's software optimizations, CPU only."""
        return cls(name="MI+SW", kind="multi_instance").replace(**kw)

    @classmethod
    def mi_sw_hb(cls, **kw) -> "SystemSpec":
        """MI+SW on a hypothetical 8x off-chip bandwidth system."""
        return cls(name="MI+SW+HB", kind="multi_instance",
                   hw=HB_PARAMS).replace(**kw)

    @classmethod
    def pim_only(cls, **kw) -> "SystemSpec":
        """Everything on general-purpose PIM cores (txn islands included)."""
        return cls(name="PIM-Only", kind="multi_instance",
                   propagation_on_pim=True, analytics_on_pim=True,
                   txn_on_pim=True).replace(**kw)

    @classmethod
    def si_ss(cls, **kw) -> "SystemSpec":
        """Single instance (NSM), software full-copy snapshots."""
        return cls(name="SI-SS", kind="si_ss").replace(**kw)

    @classmethod
    def si_mvcc(cls, **kw) -> "SystemSpec":
        """Single instance (NSM), MVCC version chains."""
        return cls(name="SI-MVCC", kind="si_mvcc").replace(**kw)

    @classmethod
    def ideal_txn(cls, **kw) -> "SystemSpec":
        """Transactions alone — the txn normalization baseline."""
        return cls(name="Ideal-Txn", kind="ideal_txn").replace(**kw)

    @classmethod
    def ana_only(cls, **kw) -> "SystemSpec":
        """Analytics alone on the multicore CPU over a DSM replica."""
        return cls(name="Ana-Only", kind="ana_only").replace(**kw)


# Preset registry: name -> zero-arg-callable factory (accepting overrides).
# The paper's six systems first (the old ALL_SYSTEMS order), then the two
# normalization baselines.
PRESETS: dict[str, Callable[..., SystemSpec]] = {
    "SI-SS": SystemSpec.si_ss,
    "SI-MVCC": SystemSpec.si_mvcc,
    "MI+SW": SystemSpec.mi_sw,
    "MI+SW+HB": SystemSpec.mi_sw_hb,
    "PIM-Only": SystemSpec.pim_only,
    "Polynesia": SystemSpec.polynesia,
}
BASELINE_PRESETS: dict[str, Callable[..., SystemSpec]] = {
    "Ideal-Txn": SystemSpec.ideal_txn,
    "Ana-Only": SystemSpec.ana_only,
}
ALL_PRESETS: dict[str, Callable[..., SystemSpec]] = {**PRESETS,
                                                    **BASELINE_PRESETS}


def resolve_spec(system: str | SystemSpec, **overrides) -> SystemSpec:
    """Preset name or spec -> spec, with keyword overrides applied."""
    if isinstance(system, SystemSpec):
        return system.replace(**overrides) if overrides else system
    try:
        factory = ALL_PRESETS[system]
    except KeyError:
        raise KeyError(f"unknown system preset {system!r}; "
                       f"have {sorted(ALL_PRESETS)}") from None
    return factory(**overrides)


def _resolve_islands(backend, n_shards, placement, hw: HardwareParams):
    """Resolve the execution backend (wrapping in Sharded/MeshBackend when
    n_shards/REPRO_SHARDS and placement/REPRO_PLACEMENT ask for islands)
    and scale the hardware model to the island count — each analytical
    island brings its own stack of in-memory hardware (§4), so
    `hw.n_ana_islands` follows the shard count unless the caller already
    set it."""
    be = get_backend(backend, n_shards=n_shards, placement=placement)
    islands = getattr(be, "n_shards", 1)
    if islands > 1 and hw.n_ana_islands == 1:
        hw = dataclasses.replace(hw, n_ana_islands=islands)
    return be, hw


def _cid_span(chunk: UpdateStream) -> tuple[int, int]:
    """(first, last) commit id of a chunk (-1, -1 when empty)."""
    if not len(chunk):
        return -1, -1
    return int(chunk.commit_id[0]), int(chunk.commit_id[-1])


class HTAPSession:
    """One long-lived HTAP system instance accepting incremental traffic.

    The session owns the storage engines of its spec's system kind plus one
    `CostLog`; `finish()` prices the log under the spec's timing model into
    an `htap.RunResult`. Drive it with any interleaving of

    * ``execute(chunk)`` — a contiguous, commit-ordered slice of the
      update stream (chunks must arrive in commit order; empty chunks are
      legal and open a zero-cost txn node),
    * ``query(q)`` / ``query_batch(queries)`` — analytical queries over
      everything executed so far (a batch runs same-column-set queries as
      fused groups, sharing pinned snapshots and resident ShardedViews),
    * ``advance_round()`` — an explicit round boundary: the point where
      synchronous propagation may stall the next round's transactions and
      where SI-MVCC queries refresh their snapshot timestamp.

    Visibility semantics per kind match the batch drivers exactly: the MI
    family applies every pending update before answering a batch
    (end-of-round freshness), SI-SS memcpy-snapshots the row store at the
    batch, SI-MVCC answers at the current round's *start* timestamp
    (concurrent-query staleness, §3.1), Ana-Only reads the initial table.
    """

    def __init__(self, spec: SystemSpec, table: np.ndarray,
                 record_spans: bool = False):
        self.spec = spec
        # start from a clean jit-trace ledger so finish()'s
        # stats["traces"] covers exactly THIS session's lifetime (ad-hoc
        # kernel calls between sessions never leak into it); the same for
        # the scans' decode counts of counters()
        from repro.kernels.common import (reset_decode_counts,
                                          reset_kernel_trace_counts)
        reset_kernel_trace_counts()
        reset_decode_counts()
        self.timing = resolve_timing(spec.timing)
        if spec.async_propagation and self.timing != "timeline":
            raise ValueError(
                "async_propagation requires timing='timeline' (the "
                "phase-bucket model has no round boundaries to overlap)")
        self.cost = CostLog()
        if record_spans:   # set-up included (`CostLog.record_spans`)
            self.cost.record_spans()
        self.round = 0
        self.results: list[int] = []
        self.n_txn = 0
        self.n_ana = 0
        self._finished = False
        self._prev_txn: str | None = None   # last txn node (dependency chain)
        self._txn_i = 0                      # txn sub-chunks this round
        self._ana_i = 0                      # per-round query/group counter
        self._snap_i = 0                     # per-round SI-SS snapshot nodes
        hw = spec.hw
        kind = spec.kind
        if kind in ("multi_instance", "ana_only"):
            self.be, hw = _resolve_islands(spec.backend, spec.n_shards,
                                           spec.placement, hw)
        else:
            # single-instance kinds: resolve once for validation and thread
            # the *resolved object* through per-query calls (no per-call
            # re-resolution of the backend spec)
            self.be = get_backend(spec.backend, n_shards=spec.n_shards,
                                  placement=spec.placement)
        self.hw = hw
        self.islands = getattr(self.be, "n_shards", 1)
        if getattr(self.be, "placement", "stacked") == "mesh":
            # make the islands' device mesh the process-global context for
            # the session's lifetime, so ad-hoc get_backend("...@N/mesh")
            # calls elsewhere in the process resolve onto the same devices;
            # finish() releases it (so does dropping the session), so a
            # later session with a different island count never sees it
            from repro.distributed import install_island_mesh
            install_island_mesh(self.be.mesh, owner=self)
        if kind == "multi_instance":
            self.store = RowStore(table)
            self.replica = DSMReplica.from_table(table)
            self.cons = ConsistencyManager(self.replica, self.cost,
                                           on_pim=spec.analytics_on_pim,
                                           backend=self.be)
            self.placement = hybrid(hw.n_vaults * hw.n_stacks)
            self.applications = 0
            self._ship_i = 0                       # global ship-batch counter
            self._vis_node: dict[int, str] = {}    # col -> last Phase-2 node
            self._round_prop: list[str] = []       # this round's apply nodes
            self._prev_round_prop: tuple[str, ...] = ()
            self.delta_enabled, self.delta_capacity = _resolve_delta(spec)
            self._deltas: dict[int, ColumnDelta] = {}  # col -> live overlay
            self.delta_appends = 0
            self.compactions = 0
            # column shards whose stage-3 re-encode ran on the device / host
            self.reencodes_device = 0
            self.reencodes_host = 0
            self._place_replica()
            # elastic island lifecycle (core/elastic.py): resize audit
            # trail + the crash-injection hook (REPRO_CRASH_AFTER arms it;
            # tests/harnesses may also set crash_after_ships directly)
            self.resizes: list[dict] = []
            from repro.core import elastic
            self.crash_after_ships = elastic.crash_after_from_env()
        elif kind == "si_ss":
            self.store = RowStore(table)
            self.snap = SnapshotStore(table)
        elif kind == "si_mvcc":
            self.store = MVCCStore(table)
            self._round_ts: int | None = None      # round-start commit id - 1
            self._last_cid = -1                    # newest executed commit id
        elif kind == "ideal_txn":
            self.store = RowStore(table)
        elif kind == "ana_only":
            self._q_i = 0   # global query counter (rounds don't reset it)
            self.replica = DSMReplica.from_table(table)
            view = self.replica.columns
            if self.islands > 1:
                # shard the read-only replica ONCE: the islands' resident
                # shards for the whole session (no updates invalidate them)
                view = {c: self.be.shard_view(col)
                        for c, col in self.replica.columns.items()}
            self._view = view

    def _place_replica(self) -> None:
        """Put the replica where its update plane applies it (MI family),
        once, as set-up (also after an `elastic` resize or restore):
        nothing on the hot path moves a whole column across.

        On the eager plane of an accelerator backend the columns live on
        the device: one stacked `pallas` replica keeps its codes and
        validity there, and islands on a device mesh (`MeshBackend`) each
        hold their shard on their own device, the column then being the
        resident `ShardedView` that the first pinned read adopts. Stage 3
        runs where the column lives (`application.apply_updates_where`),
        and the consistency manager keeps each column's self-join build
        side (`BuildSide`). Every other plane applies host columns, and
        its joins count their build side from the column."""
        be, cols = self.be, self.replica.columns
        if not (self.spec.optimized_application and not self.delta_enabled
                and isinstance(be, (PallasBackend, MeshBackend))):
            self.cons.build_sides.clear()
            for c, col in cols.items():
                if isinstance(col, ShardedView):
                    cols[c] = host_column(col)
            return
        self.cons.keep_build_sides()
        with self.cost.span("place",
                            n=sum(col.n_rows for col in cols.values())):
            for c, col in cols.items():
                if isinstance(be, MeshBackend):
                    # sharded on the host here, and never again
                    cols[c] = be.shard_view(host_column(col))
                    continue
                if isinstance(col, ShardedView):
                    col = host_column(col)
                if not isinstance(col.codes, jax.Array):
                    col = EncodedColumn(
                        codes=jax.device_put(col.codes),
                        dictionary=col.dictionary,
                        valid=jax.device_put(col.valid), version=col.version)
                cols[c] = col

    # -- lifecycle ---------------------------------------------------------
    def _check_open(self) -> None:
        if self._finished:
            raise SessionClosedError(
                "HTAPSession is finished; start a new session for more "
                "traffic")

    def advance_round(self) -> None:
        """Close the current round and open the next.

        For the MI family this is where synchronous propagation bites: the
        next round's first txn chunk carries ``sync_deps`` on this round's
        Phase-2 applies (dropped under async propagation). For SI-MVCC the
        next round's queries snapshot at the next chunk's start timestamp.
        """
        self._check_open()
        self.round += 1
        self._txn_i = 0
        self._ana_i = 0
        self._snap_i = 0
        if self.spec.kind == "multi_instance":
            self._prev_round_prop = tuple(self._round_prop)
            self._round_prop = []
        elif self.spec.kind == "si_mvcc":
            self._round_ts = None

    def finish(self) -> "htap.RunResult":  # noqa: F821 (circular import)
        """Price the accumulated cost log -> RunResult (closes the session)."""
        self._check_open()
        self._finished = True
        # release the process-global mesh context installed in __init__:
        # the mesh installed before it (another live session's) is back
        from repro.distributed import release_island_mesh
        release_island_mesh(self)
        from repro.core import htap
        spec = self.spec
        stats: dict = {}
        concurrent = spec.kind not in ("ideal_txn", "ana_only")
        if spec.kind == "multi_instance":
            counts = self.counters()
            stats = {key: counts[name]
                     for name, key in FINISH_STATS.items()}
            stats["islands"] = self.islands
            stats["placement"] = getattr(self.be, "placement", "stacked")
            if self.delta_enabled:
                stats["delta_appends"] = counts["delta_appends"]
                stats["compactions"] = counts["compactions"]
                stats["delta_live_entries"] = sum(
                    d.n_overlay for d in self._deltas.values())
            if self.resizes:
                stats["resizes"] = [dict(r) for r in self.resizes]
        elif spec.kind == "si_ss":
            stats = {"snapshots": self.snap.snapshots_taken}
        elif spec.kind == "si_mvcc":
            stats = {"versions": self.store.n_versions}
        # per-entry-point jit trace counts accumulated over the session's
        # lifetime (kernels.common.instrumented_jit): a warm steady state
        # shows zero retraces across rounds — surfaced for the CI trace
        # artifact and the zero-retrace tests, then reset so the next
        # session starts from a clean ledger
        from repro.kernels.common import (kernel_trace_counts,
                                          reset_kernel_trace_counts)
        stats["traces"] = dict(kernel_trace_counts())
        reset_kernel_trace_counts()
        return htap._price(spec.name, self.cost, self.hw, self.timing,
                           self.n_txn, self.n_ana, self.results, stats=stats,
                           async_propagation=spec.async_propagation,
                           concurrent_islands=concurrent)

    def counters(self) -> dict:
        """The session's work counts so far, flat, read without closing
        it: ``query_groups`` (query-group timeline nodes, every round),
        ``kernel_traces`` (jit traces of the kernel entry points), and on
        the MI family ``ships``, the application, compaction, snapshot
        and view counts that `finish` reports, and ``reencodes_device`` /
        ``reencodes_host``: the column shards (one per island, one for an
        unsharded column) whose stage-3 re-encode ran on the device / on
        the host. ``scan_decodes_select`` / ``_hist`` / ``_gather`` count
        the scans' column decodes by path (`dict_ops.decode_path`; an
        aggregate column and a join build side are one each)."""
        from repro.kernels.common import decode_counts, kernel_trace_counts
        out = {"query_groups": sum(t.kind == "ana"
                                   for t in self.cost.tags.values()),
               "kernel_traces": sum(kernel_trace_counts().values())}
        out.update((f"scan_decodes_{path}", n)
                   for path, n in decode_counts().items())
        if self.spec.kind == "multi_instance":
            cons = self.cons
            out.update(ships=self._ship_i, applications=self.applications,
                       compactions=self.compactions,
                       delta_appends=self.delta_appends,
                       snapshots_created=cons.snapshots_created,
                       snapshots_shared=cons.snapshots_shared,
                       views_built=cons.views_built,
                       views_shared=cons.views_shared,
                       views_resident=cons.views_resident,
                       reencodes_device=self.reencodes_device,
                       reencodes_host=self.reencodes_host)
        return out

    def abort(self) -> None:
        """Close the session without pricing (no RunResult) — the clean-up
        path after an injected `elastic.SessionCrash` (or any abandoned
        session): releases the process-global mesh context and resets the
        jit-trace ledger, exactly like `finish()`, but produces nothing.
        Idempotent; a later `finish()` raises `SessionClosedError`."""
        if self._finished:
            return
        self._finished = True
        from repro.distributed import release_island_mesh
        release_island_mesh(self)
        from repro.kernels.common import reset_kernel_trace_counts
        reset_kernel_trace_counts()

    # -- elastic lifecycle (core/elastic.py) -------------------------------
    def resize_islands(self, n_islands: int,
                       placement: str | None = None) -> str | None:
        """Online resharding: repartition the analytical islands to
        ``n_islands`` at this round boundary (MI family only). Answer-
        neutral; the rebalance is priced as a ``reshard`` node on the
        fixed-function lane. See `core.elastic.resize_islands`."""
        from repro.core import elastic
        return elastic.resize_islands(self, n_islands, placement=placement)

    def checkpoint(self, ckpt_dir: str, step: int | None = None) -> int:
        """Serialize the full session state into ``ckpt_dir`` through the
        atomic-commit checkpoint layout. See
        `core.elastic.checkpoint_session`."""
        from repro.core import elastic
        return elastic.checkpoint_session(self, ckpt_dir, step=step)

    @classmethod
    def restore(cls, ckpt_dir: str, spec: SystemSpec | None = None,
                step: int | None = None) -> "HTAPSession":
        """Rebuild a session from the last committed checkpoint, optionally
        onto a *different* spec (backend / shard count / placement — the
        elastic-restart path). See `core.elastic.restore_session`."""
        from repro.core import elastic
        return elastic.restore_session(ckpt_dir, spec=spec, step=step)

    # -- transactional surface ---------------------------------------------
    def execute(self, chunk: UpdateStream) -> None:
        """Execute a contiguous commit-ordered chunk of transactions.

        Opens one txn timeline node per call (chained after the previous
        one; the round's first chunk also waits on the previous round's
        propagation under synchronous timing). On the MI family, capacity-
        triggered update shipping runs here: whenever the pending updates
        reach the final log's capacity, a ship batch leaves for the
        analytical island.
        """
        self._check_open()
        kind = self.spec.kind
        if kind == "ana_only":
            raise ValueError("Ana-Only has no transactional island; "
                             "this spec only accepts queries")
        node = (f"r{self.round}:txn" if self._txn_i == 0
                else f"r{self.round}:txn.{self._txn_i}")
        self._txn_i += 1
        lo, hi = _cid_span(chunk)
        deps = (self._prev_txn,) if self._prev_txn else ()
        if kind == "multi_instance":
            sync_deps = self._prev_round_prop if self._txn_i == 1 else ()
            with self.cost.tagged(node, "txn", round=self.round, deps=deps,
                                  sync_deps=sync_deps, n=len(chunk),
                                  cid_lo=lo, cid_hi=hi):
                self._execute_mi(chunk)
        else:
            with self.cost.tagged(node, "txn", round=self.round, deps=deps,
                                  n=len(chunk), cid_lo=lo, cid_hi=hi):
                self.store.execute(chunk, self.cost)
        self._prev_txn = node
        self.n_txn += len(chunk)
        if kind == "si_ss":
            self.snap.data = self.store.data   # single instance: same storage
            if chunk.writes_mask().any():
                self.snap.mark_dirty()
        elif kind == "si_mvcc":
            if self._round_ts is None and len(chunk):
                # queries this round snapshot at the round's start (§3.1):
                # every version the round commits must be hopped over
                self._round_ts = int(chunk.commit_id[0]) - 1
            if len(chunk):
                self._last_cid = int(chunk.commit_id[-1])
        elif kind == "multi_instance":
            # §5: ship when the final log's hardware capacity is reached
            while self.store.pending_updates >= FINAL_LOG_CAPACITY:
                self._ship_once()

    def _execute_mi(self, chunk: UpdateStream) -> None:
        if self.spec.txn_on_pim:
            self.store.execute(chunk)  # functional only; price on PIM:
            n = len(chunk)
            self.cost.add(phase="txn", island="txn", resource="pim_txn",
                          cycles=n * RowStore.CYCLES_PER_TXN
                          * PIM_TXN_CYCLE_FACTOR,
                          bytes_local=n * self.store.n_cols * 4
                          * RowStore.MISS_FRACTION)
        else:
            self.store.execute(chunk, self.cost)

    # -- update propagation (§5, MI family) --------------------------------
    def _ship_once(self) -> None:
        """One ship batch: drain -> merge/locate/ship -> per-column apply.

        The final log is a hardware buffer (§5.1's merge unit): when
        propagation runs on the in-memory units, each batch is at most one
        final log's worth — larger capacity means fewer, staler batches.
        The software baseline has no such structure and ships its whole
        backlog at once.
        """
        spec = self.spec
        # fault injection (REPRO_CRASH_AFTER / crash_after_ships): the
        # "process" dies before this batch leaves — executed-but-unshipped
        # updates survive only in the row store + logs, which is exactly
        # the state a checkpoint captures and crash recovery replays
        from repro.core import elastic
        elastic.maybe_crash(self)
        limit = FINAL_LOG_CAPACITY if spec.propagation_on_pim else None
        n_updates = self.store.pending_updates
        ship_node = f"r{self.round}:ship{self._ship_i}"
        with self.cost.span("ship_batch", node=ship_node,
                            n=n_updates if limit is None
                            else min(n_updates, limit)):
            with self.cost.span("drain"):
                logs = self.store.drain_logs(limit=limit)
            self._ship_i += 1
            # in sync timing the batch waits for the txn execution that
            # filled it; async releases it at its last update's commit time
            sync_deps = (self._prev_txn,) if self._prev_txn else ()
            with self.cost.tagged(ship_node, "ship", round=self.round,
                                  sync_deps=sync_deps, islands=self.islands):
                # the batch's commit-id span and size are annotated on the
                # tag even when the Ideal baseline suppresses pricing —
                # freshness and async release times are metadata, not cost
                buffers = ship_updates(logs, self.store.n_cols, self.cost,
                                       on_pim=spec.propagation_on_pim,
                                       backend=self.be,
                                       price=not spec.zero_cost_propagation)
            # The whole batch's dictionary stages ride one sorter dispatch
            # and one merge dispatch (cost events stay per column below —
            # tags are structural, and the cost model is analytic, not
            # measured). The delta plane skips the precompute: eligible
            # batches never touch the dictionary, and the rare fallback
            # stages its own merge.
            staged = {}
            if (spec.optimized_application and len(buffers) > 1
                    and not self.delta_enabled):
                with self.cost.span("stages"):
                    staged = precompute_apply_stages(self.replica.columns,
                                                     buffers, backend=self.be)
            app_cost = (None if (spec.shipping_only
                                 or spec.zero_cost_propagation)
                        else self.cost)
            for col_id, entries in buffers.items():
                if self.delta_enabled:
                    self._apply_column_delta(col_id, entries, ship_node,
                                             app_cost)
                else:
                    apply_node = f"{ship_node}:c{col_id}"
                    self._apply_column_eager(col_id, entries, apply_node,
                                             app_cost, staged.get(col_id),
                                             deps=(ship_node,))
                    self._vis_node[col_id] = apply_node
                    self._round_prop.append(apply_node)
                    self.applications += 1

    def _apply_column_eager(self, col_id: int, entries: np.ndarray,
                            node: str, app_cost, staged_col, deps,
                            kind: str = "apply",
                            phase: str = "apply") -> None:
        """One column's batch through the standard two-stage apply (Phase-2
        swap via the consistency manager). Also the compaction executor:
        kind/phase "compact" reuses the exact same machinery, so the folded
        base is bit-identical to what eager application would have built."""
        spec = self.spec
        old = self.replica.columns[col_id]
        with self.cost.tagged(node, kind, round=self.round, deps=deps,
                              col=col_id, islands=self.islands):
            mesh = getattr(self.be, "placement", "stacked") == "mesh"
            where, shards = "host", 1
            if (spec.optimized_application and (self.islands > 1 or mesh)
                    and not isinstance(old, ShardedView)):
                # each island applies its own row range of a host column;
                # the round becomes visible only as a complete shard set
                # (all-or-none Phase-2 swap)
                new = apply_updates_shards(
                    old, entries, app_cost,
                    on_pim=spec.propagation_on_pim, backend=self.be,
                    staged=staged_col, phase=phase)
                swap, shards = self.cons.on_update_shards, len(new)
            elif spec.optimized_application:
                # one column, or islands resident on their devices, whose
                # new shards install as one view
                new, where = apply_updates_where(
                    old, entries, app_cost,
                    on_pim=spec.propagation_on_pim, backend=self.be,
                    staged=staged_col, phase=phase)
                shards = getattr(new, "n_shards", 1)

                def swap(c, new):
                    self.cons.on_update(c, new, updates=entries)
            else:
                # the naive software baseline rebuilds a whole column
                new = apply_updates_naive(old, entries, app_cost,
                                          phase=phase)
                swap = self.cons.on_update
            if where == "device":
                self.reencodes_device += shards
            else:
                self.reencodes_host += shards
            with self.cost.span("swap"):
                swap(col_id, new)

    def _apply_column_delta(self, col_id: int, entries: np.ndarray,
                            ship_node: str, app_cost) -> None:
        """Delta-plane Phase 2: append the batch to the column's overlay.

        The append is O(batch + overlay) — the base column is untouched —
        so the apply node the next round's transactions stall on is cheap:
        that is the freshness/throughput win at high commit rates. When the
        overlay's raw entry count crosses the capacity threshold, a
        background compaction node (kind "compact", priced on the
        analytical island's accelerators, so it overlaps analytics and
        never joins the sync stall set) folds it into the base through the
        standard apply path and resets the overlay.
        """
        old = self.replica.columns[col_id]
        delta = self._deltas.get(col_id)
        if delta is None or delta.n_base != old.n_rows:
            delta = empty_delta(old)
        apply_node = f"{ship_node}:c{col_id}"
        if not delta_eligible(entries, old.n_rows):
            # inserts / out-of-range writes resize the column, which the
            # overlay algebra does not model: fold the overlay first
            # (commit order), then eager-apply the batch
            deps = (ship_node,)
            if delta.n_overlay:
                comp = self._compact_column(col_id, delta, deps=deps,
                                            ship_node=ship_node)
                deps = (ship_node, comp)
            self._apply_column_eager(col_id, entries, apply_node, app_cost,
                                     None, deps=deps)
            self._deltas[col_id] = empty_delta(self.replica.columns[col_id])
        else:
            with self.cost.tagged(apply_node, "apply", round=self.round,
                                  deps=(ship_node,), col=col_id,
                                  islands=self.islands):
                delta = apply_updates_delta(
                    old, delta, entries, app_cost,
                    on_pim=self.spec.propagation_on_pim, backend=self.be)
            self._deltas[col_id] = delta
            self.delta_appends += 1
        self._vis_node[col_id] = apply_node
        self._round_prop.append(apply_node)
        self.applications += 1
        delta = self._deltas[col_id]
        if delta.n_entries >= self.delta_capacity and delta.n_overlay:
            self._compact_column(col_id, delta, deps=(apply_node,),
                                 ship_node=ship_node)

    def _compact_column(self, col_id: int, delta: ColumnDelta, deps,
                        ship_node: str) -> str:
        """Fold a column's overlay into its base (background compaction).

        Synthesizes the overlay's write/delete entries (commit-id ordered)
        and runs them through the standard two-stage apply, so the
        compacted base goes through the usual Phase-2 snapshot-chain swap.
        The node is deliberately NOT added to ``_round_prop``: compaction
        is priced on the accel lane and overlaps analytics instead of
        stalling the next round's transactions. Queries still wait for it
        (``_vis_node``) — they read the compacted base.
        """
        spec = self.spec
        app_cost = (None if (spec.shipping_only
                             or spec.zero_cost_propagation)
                    else self.cost)
        node = f"{ship_node}:compact{col_id}"
        entries = compaction_entries(delta, col_id)
        self._apply_column_eager(col_id, entries, node, app_cost, None,
                                 deps=deps, kind="compact", phase="compact")
        self._deltas[col_id] = empty_delta(self.replica.columns[col_id])
        self._vis_node[col_id] = node
        self.compactions += 1
        return node

    def flush_updates(self) -> None:
        """Ship and apply the entire pending update backlog now.

        `query_batch` pulls this implicitly (queries must see everything
        executed before them); it is public for drivers that want
        propagation *without* analytics — e.g. the Fig. 3 breakdown, which
        measures the txn island's shipping/application shares with the
        query cores silent. MI family only: the single-instance baselines
        have no replica to propagate to.
        """
        self._check_open()
        if self.spec.kind != "multi_instance":
            raise ValueError(
                f"flush_updates is a multiple-instance mechanism; "
                f"{self.spec.name!r} is kind {self.spec.kind!r}")
        while self.store.pending_updates:
            self._ship_once()

    # -- analytical surface ------------------------------------------------
    def query(self, q: engine.Query) -> int:
        """Answer one analytical query over the currently visible data."""
        return self.query_batch([q])[0]

    def query_batch(self, queries: list[engine.Query]) -> list[int]:
        """Answer a batch of analytical queries (fused same-column groups).

        An empty batch is a no-op (it does not flush pending updates). On
        the MI family a non-empty batch first drains the remaining update
        backlog — queries see everything executed before them — then runs
        each same-column-set group as one fused multi-query scan over a
        shared pinned snapshot (one batched launch across all islands).
        """
        self._check_open()
        queries = list(queries)
        if not queries:
            return []
        kind = self.spec.kind
        if kind == "ideal_txn":
            raise ValueError("Ideal-Txn has no analytical island; "
                             "this spec only accepts transactions")
        answers = {
            "multi_instance": self._query_batch_mi,
            "si_ss": self._query_batch_si_ss,
            "si_mvcc": self._query_batch_si_mvcc,
            "ana_only": self._query_batch_ana_only,
        }[kind](queries)
        self.results.extend(answers)
        self.n_ana += len(queries)
        return answers

    def _query_batch_mi(self, queries) -> list[int]:
        # flush the whole backlog first: a query batch is the §5 trigger
        # that makes every committed update visible (end-of-round contract)
        self.flush_updates()
        batch_results: dict[int, int] = {}
        for group in engine.group_queries(queries):
            g = self._ana_i
            self._ana_i += 1
            cols = group[0].columns
            snap_node = f"r{self.round}:snap{g}"
            snap_deps = tuple(dict.fromkeys(
                self._vis_node[c] for c in cols if c in self._vis_node))
            # islands= prices the node at the CURRENT island count on the
            # timeline (resize-aware: core/timeline.py builds a per-count
            # model when it differs from the final hw); n= is the group's
            # query count, feeding the per-query latency percentiles
            with self.cost.tagged(snap_node, "snapshot", round=self.round,
                                  deps=snap_deps, islands=self.islands):
                handles, view = self.cons.pin_scan_group(
                    [q.columns for q in group])
            join_col = group[0].join_col
            with self.cost.tagged(f"r{self.round}:ana{g}", "ana",
                                  round=self.round, deps=(snap_node,),
                                  islands=self.islands, n=len(group)):
                # delta plane: scans merge the pinned base with each
                # column's live overlay (appends never dirty the snapshot
                # chain, so the pinned base IS the overlay's base)
                group_answers = engine.run_query_group_dsm(
                    view, group, self.cost, self.placement,
                    on_pim=self.spec.analytics_on_pim, backend=self.be,
                    deltas=self._deltas if self.delta_enabled else None,
                    base_cols=(self.replica.columns
                               if self.delta_enabled else None),
                    rcount=(None if join_col is None else
                            self.cons.build_counts(handles[0], join_col)))
            for q, a in zip(group, group_answers):
                batch_results[id(q)] = a
            for h in handles:
                self.cons.end_query(h)
        return [batch_results[id(q)] for q in queries]

    def _query_batch_si_ss(self, queries) -> list[int]:
        # the memcpy burns txn-island CPU -> the snapshot node lands in
        # the txn lane, which is exactly the Fig. 1-right stall
        snap_node = (f"r{self.round}:snap" if self._snap_i == 0
                     else f"r{self.round}:snap.{self._snap_i}")
        self._snap_i += 1
        deps = (self._prev_txn,) if self._prev_txn else ()
        with self.cost.tagged(snap_node, "snapshot", round=self.round,
                              deps=deps):
            view = self.snap.take_snapshot_if_needed(
                None if self.spec.zero_cost_snapshot else self.cost)
        answers = []
        for q in queries:
            i = self._ana_i
            self._ana_i += 1
            with self.cost.tagged(f"r{self.round}:ana{i}", "ana",
                                  round=self.round, deps=(snap_node,)):
                answers.append(engine.run_query_nsm(view, q, self.cost,
                                                    backend=self.be))
        return answers

    def _query_batch_si_mvcc(self, queries) -> list[int]:
        # analytics run CONCURRENTLY with this round's transactions: the
        # snapshot timestamp is the round start, so every version committed
        # during the round is "newer" and must be hopped over (§3.1). On
        # the timeline the query nodes therefore depend only on the
        # previous round's txn nodes.
        # a round with no transactions (yet) snapshots at "now": everything
        # committed in earlier rounds is visible, nothing is hopped over
        ts = self._round_ts if self._round_ts is not None else self._last_cid
        hops = not self.spec.zero_cost_mvcc
        deps = ()
        if self.round:
            prev = self._mvcc_prev_round_txn
            if prev is not None:
                deps = (prev,)
        answers = []
        for q in queries:
            i = self._ana_i
            self._ana_i += 1
            with self.cost.tagged(f"r{self.round}:ana{i}", "ana",
                                  round=self.round, deps=deps):
                store = self.store
                fvals = store.read_column_at(q.filter_col, ts, self.cost,
                                             hops)
                avals = store.read_column_at(q.agg_col, ts, self.cost, hops)
                mask = (fvals >= q.lo) & (fvals <= q.hi)
                res = int(avals[mask].astype(np.int64).sum())
                if q.join_col is not None:
                    jv = store.read_column_at(q.join_col, ts, self.cost,
                                              hops)
                    uv, counts = np.unique(jv, return_counts=True)
                    lv, lcounts = np.unique(jv[mask], return_counts=True)
                    common, li, ri = np.intersect1d(lv, uv,
                                                    return_indices=True)
                    res += int((lcounts[li].astype(np.int64)
                                * counts[ri]).sum())
                answers.append(res)
                # scan cycles beyond chain traversal (already priced in
                # read_column_at)
                self.cost.add(phase="ana", island="ana", resource="cpu",
                              cycles=store.base.shape[0]
                              * engine.CPU_CYCLES_PER_ROW)
        return answers

    @property
    def _mvcc_prev_round_txn(self) -> str | None:
        # the last txn node of any PREVIOUS round (queries run concurrently
        # with the current round's transactions, so they never wait on
        # them): when this round already executed chunks, that is the
        # dependency of the round's first chunk; otherwise the chain tail.
        if self._txn_i:
            tag = self.cost.tags[f"r{self.round}:txn"]
            return tag.deps[0] if tag.deps else None
        return self._prev_txn

    def _query_batch_ana_only(self, queries) -> list[int]:
        answers = []
        for q in queries:
            # globally numbered: q{i} node names must stay unique across
            # rounds (advance_round resets only the per-round counters)
            i = self._q_i
            self._q_i += 1
            with self.cost.tagged(f"q{i}:ana", "ana", round=self.round):
                answers.append(engine.run_query_dsm(self._view, q, self.cost,
                                                    on_pim=False,
                                                    backend=self.be))
        return answers
