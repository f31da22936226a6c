"""Update application (§5.2): NSM->DSM conversion under dictionary encoding.

Two algorithms, both functionally exact:

* `apply_updates_naive` — the paper's *initial* algorithm: decompress the
  whole column, apply updates, sort the updated column to rebuild the
  dictionary (O((n+m)log(n+m))), recompress with per-entry binary search.
  Kept as the costed baseline and as the oracle for property tests.

* `apply_updates` — the paper's *optimized* two-stage algorithm:
    1. bitonic-sort only the <=1024 pending update values into an *update
       dictionary* (sort unit; Pallas analog kernels/bitonic_sort),
    2. linear-merge old + update dictionaries (merge unit) and build a hash
       index old_code -> new_code,
    3. re-encode the column through the index (sequential scan, no random
       dictionary lookups) and scatter the update values' new codes at
       their rows (hash unit prices the update-value encodes). On a
       device-resident column (`apply_updates_where`) the index is never
       gathered: the map is monotone, so each old code moves up by the
       number of new values below it, a compare-and-add on the device
       (kernels/reencode), and the column stays on the device.
  Random accesses drop from O((n+m)log(n+m)) to O(n+m), which is the claim
  we verify in benchmarks/fig3 and tests/test_update_application.py.

Phase 2 of the consistency contract (§6): the function returns a *new*
EncodedColumn with `version+1`; the caller atomically swaps the replica
pointer (functional update), so analytics never observe a half-applied
column.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.core.backend import (PallasBackend, ShardedBackend, _fits_int32,
                                get_backend)
from repro.core.dsm import (ColumnDelta, EncodedColumn, ShardedView,
                            host_column, new_values, shard_bounds)
from repro.core.hwmodel import CostLog, span
from repro.core.nsm import UPDATE_DTYPE
from repro.core.schema import VALUE_BYTES
from repro.kernels.merge_runs import merge_sorted_runs

# software (CPU) costs for the same steps, for the MI baseline
CPU_CYCLES_PER_CMP = 8.0
CPU_CYCLES_PER_LOOKUP = 30.0   # random dictionary access (cache-missing)
CPU_CYCLES_PER_SCAN_ITEM = 3.0
# One delta-overlay entry: row id (8) + value (4) + cid (8) + valid/pad (4)
DELTA_ENTRY_BYTES = 24
# Soft partitioning (§5.1, [49,51,62]): columns are partitioned so the
# dictionary/hash-table working set stays bounded; an update batch touches
# only the partitions containing its rows, so (de)compression cost scales
# with the partition, not the whole column.
PARTITION_ROWS = 4096


def _split_ops(updates: np.ndarray):
    mods = updates[updates["op"] == 1]
    ins = updates[updates["op"] == 2]
    dels = updates[updates["op"] == 3]
    return mods, ins, dels


def _sorted_write_ops(mods: np.ndarray, ins: np.ndarray) -> np.ndarray:
    """Modify+insert entries in commit order — the scatter order of the
    Phase-1 write set (shared by the direct and pre-encoded paths so the
    two can never drift apart)."""
    write_ops = np.concatenate([mods, ins]) if len(ins) else mods
    if len(write_ops):
        order = np.argsort(write_ops["commit_id"], kind="stable")
        write_ops = write_ops[order]
    return write_ops


def _apply_row_ops(codes: np.ndarray, valid: np.ndarray, new_dict: np.ndarray,
                   mods: np.ndarray, ins: np.ndarray, dels: np.ndarray,
                   encode=None, write_set=None):
    """Scatter modify/insert/delete row ops in commit order (vectorized).

    `encode` maps update values to their codes in `new_dict` (§5.2's hash
    unit on the accelerator backend); defaults to binary search.
    `write_set`, when given, is a ``(write_ops, write_codes)`` pair: the
    commit-ordered write set (`_sorted_write_ops(mods, ins)`) together
    with its pre-encoded codes — the sharded path batches all islands'
    encodes into one probe launch and hands each island its pair here, so
    the scatter order and the codes come from the same materialization.
    """
    if encode is None:
        encode = lambda v: np.searchsorted(new_dict, v)
    if len(ins):
        # Inserts append rows; their per-column values arrive as entries with
        # row >= n. Extend the arrays to cover the max inserted row id.
        top = int(ins["row"].max()) + 1
        if top > len(codes):
            pad = top - len(codes)
            codes = np.concatenate([codes, np.zeros(pad, dtype=codes.dtype)])
            valid = np.concatenate([valid, np.zeros(pad, dtype=bool)])
    if write_set is not None:
        write_ops, write_codes = write_set
    else:
        write_ops, write_codes = _sorted_write_ops(mods, ins), None
    if len(write_ops):
        new_codes_for_writes = (write_codes if write_codes is not None
                                else encode(write_ops["value"]))
        codes[write_ops["row"]] = new_codes_for_writes.astype(codes.dtype)
        valid[write_ops["row"]] = True
    if len(dels):
        valid[dels["row"]] = False
    return codes, valid


def _last_writes(write_ops: np.ndarray, write_codes: np.ndarray):
    """One ``(row, code)`` per written row, its last write in commit
    order (`write_ops` is commit-ordered): a device scatter's order over
    duplicate rows is undefined, so the duplicates go first."""
    rows = write_ops["row"]
    if len(rows) < 2:
        return rows, write_codes
    _, first_of_reversed = np.unique(rows[::-1], return_index=True)
    last = len(rows) - 1 - first_of_reversed
    return rows[last], write_codes[last]


def _merge_dictionary_stages_batch(be, per_column):
    """Stages 1-2 of the optimized application for every column of a ship
    batch at once. This is the ONE code path behind both the unsharded and
    sharded applies (their bit-identity contract depends on that): per
    column, sort+dedupe the pending update values (1024-value sorter),
    linear-merge the sorted dictionaries (merge unit), and build the
    hash-unit encoder over the merged dictionary. The backend *_batch ops
    ride all columns' sorts as rows of one sorter dispatch and all
    dictionary merges as rows of one merge dispatch.

    `per_column` is a list of (old_dict, write_vals); returns a list of
    (update_dict, new_dict, encode, old_to_new) in the same order. The
    old->new index is a positional byproduct of the merge — both
    dictionaries are sorted and every old value survives into the merged
    one, so each old entry's new code is its position there (the paper's
    merge unit emits the mapping during the merge pass; the staged encoder
    binary-searches the *update* values, which are all present in the
    merged dictionary by construction). All the batching is safe because
    sorts and merges are exact and item-independent — grouping them cannot
    change any individual result. The whole pipeline now lives on the
    backend (`ExecutionBackend.apply_stages_batch`): the accelerator
    backend fuses sort + merge into ONE donated-buffer launch per batch.
    """
    return be.apply_stages_batch(per_column)


def _merge_dictionary_stages(be, old_dict: np.ndarray, write_vals: np.ndarray):
    """Single-column stages 1-2: a batch of one (see the batch docstring)."""
    return _merge_dictionary_stages_batch(be, [(old_dict, write_vals)])[0]


def precompute_apply_stages(columns, buffers, backend=None) -> dict:
    """Precompute stages 1-2 for every column of a ship batch, riding all
    columns' update-value sorts on one sorter dispatch and all dictionary
    merges on one merge dispatch.

    `columns` maps col_id -> current EncodedColumn, `buffers` maps
    col_id -> that column's shipped update entries (shipping.ship_updates
    output). Returns {col_id: staged} to pass as `apply_updates(...,
    staged=...)`. With a ShardedBackend the stages run on the inner
    backend, exactly as `apply_updates_shards` would. Purely a batching
    hint: results are bit-identical to each apply computing its own
    stages, because every batched op is exact and item-independent.
    """
    be = get_backend(backend)
    inner = be.inner if isinstance(be, ShardedBackend) else be
    ids = list(buffers.keys())
    per_column = []
    for cid in ids:
        mods, ins, _ = _split_ops(buffers[cid])
        per_column.append((np.asarray(columns[cid].dictionary),
                           np.concatenate([mods["value"], ins["value"]])))
    return dict(zip(ids, _merge_dictionary_stages_batch(inner, per_column)))


def route_updates(updates: np.ndarray, bounds: list[int]) -> np.ndarray:
    """Owning-shard id for each update, routed by row id.

    `bounds` are contiguous shard boundaries (dsm.shard_bounds over the
    post-insert row count); rows at or past the last boundary (fresh
    inserts) belong to the last shard.
    """
    shard = np.searchsorted(np.asarray(bounds), updates["row"],
                            side="right") - 1
    return np.clip(shard, 0, len(bounds) - 2)


def _optimized_apply_cost(cost: CostLog, on_pim: bool, m: int, n: int,
                          k_old: int, k_new: int, n_update_dict: int,
                          bit_width: int, phase: str = "apply") -> None:
    """Cost events for the optimized two-stage application (shared by the
    unsharded and sharded paths). The sharded path emits the same events:
    the dictionary stages (sorter/merge/hash) are replicated per island so
    their modeled latency is island-independent, while the stage-3
    re-encode bytes are row-partitioned and ride the island-scaled copy/
    bandwidth rates (see hwmodel.phase_time). `phase` distinguishes the
    foreground swap ("apply") from background delta compaction ("compact"):
    same events, different timeline node — freshness counts only the
    former."""
    # timeline metadata: applied-update count on this node's Phase-2 swap
    cost.annotate_add(n_applied=int(m))
    # soft partitioning: updates touch at most m partitions
    n_eff = min(n, max(1, min(m, n // PARTITION_ROWS + 1)) * PARTITION_ROWS)
    enc_eff = n_eff * bit_width / 8.0
    if on_pim:
        cost.add(phase=phase, island="ana", resource="sorter", items=m)
        cost.add(phase=phase, island="ana", resource="merge",
                 items=k_old + n_update_dict,
                 bytes_local=(k_old + k_new) * VALUE_BYTES)
        # index-based re-encode: one sequential pass (index fits in VMEM/SRAM)
        cost.add(phase=phase, island="ana", resource="copy",
                 bytes_local=2 * enc_eff)
        cost.add(phase=phase, island="ana", resource="hash",
                 items=m, bytes_local=m * 16)
    else:
        cost.add(
            phase=phase, island="txn", resource="cpu",
            cycles=m * np.log2(max(m, 2)) * CPU_CYCLES_PER_CMP        # sort updates
            + (k_old + k_new) * CPU_CYCLES_PER_SCAN_ITEM              # dict merge
            + n_eff * 8.0                                             # unpack+reindex+pack
            + m * CPU_CYCLES_PER_LOOKUP,                              # encode updates
            bytes_offchip=2 * enc_eff + (k_old + k_new) * VALUE_BYTES + m * 16,
        )


def apply_updates(
    col: EncodedColumn,
    updates: np.ndarray,
    cost: CostLog | None = None,
    on_pim: bool = True,
    backend=None,
    staged=None,
    phase: str = "apply",
) -> EncodedColumn:
    """Optimized two-stage update application (the paper's contribution).

    Each stage runs on the selected execution backend: the PallasBackend
    dispatches the sort to kernels/bitonic_sort, the dictionary merge to
    kernels/merge_runs and the value->code encodes to kernels/hash_probe;
    the NumpyBackend keeps the original unique/union1d/searchsorted path.
    A ShardedBackend routes row ops to their owning islands (see
    `apply_updates_shards`) — the result is bit-identical either way.

    `staged`, when given, is this column's precomputed stages 1-2 entry
    from `precompute_apply_stages` (the ship batch's cross-column sorter/
    merge batching); it MUST have been computed from this column's current
    dictionary and these updates' write values.
    """
    return apply_updates_where(col, updates, cost, on_pim, backend, staged,
                               phase)[0]


def apply_updates_where(
    col: EncodedColumn,
    updates: np.ndarray,
    cost: CostLog | None = None,
    on_pim: bool = True,
    backend=None,
    staged=None,
    phase: str = "apply",
) -> tuple[EncodedColumn | ShardedView, str]:
    """`apply_updates`, and where its stage 3 ran: ``"device"`` or
    ``"host"``. A column whose islands live on their own devices (a
    `ShardedView`) goes to `apply_updates_islands`."""
    be = get_backend(backend)
    if isinstance(col, ShardedView):
        return apply_updates_islands(col, updates, cost, on_pim, be, staged,
                                     phase)
    if isinstance(be, ShardedBackend) and be.n_shards > 1:
        from repro.core.dsm import concat_columns
        return concat_columns(apply_updates_shards(col, updates, cost,
                                                   on_pim, be,
                                                   staged=staged,
                                                   phase=phase)), "host"
    old_dict = np.asarray(col.dictionary)
    n, k_old = col.n_rows, old_dict.shape[0]
    mods, ins, dels = _split_ops(updates)
    write_vals = np.concatenate([mods["value"], ins["value"]])
    m = len(updates)
    resident = (isinstance(be, PallasBackend)
                and isinstance(col.codes, jax.Array))
    # a device stage 3 keeps the column's length and int32 values
    on_device = (resident and not len(ins)
                 and _fits_int32(mods["value"]))

    # Stages 1-2: update-dictionary sort + dictionary merge + old->new
    # index. (hardware: 1024-value bitonic sorter, merge unit; the index
    # falls out of the merge pass — see the stages docstring)
    update_dict, new_dict, encode, old_to_new = (
        staged if staged is not None
        else _merge_dictionary_stages(be, old_dict, write_vals))

    # Hash unit: encode the write set's values against the new dictionary
    # in one probe dispatch.
    write_ops = _sorted_write_ops(mods, ins)
    write_codes = encode(write_ops["value"])

    # Stage 3: sequential re-encode through the index + scatter update
    # codes — on the device for a device-resident column, where the
    # result stays; a host column stays host numpy (the jitted kernels
    # convert it at dispatch).
    with span("reencode", n=n):
        if on_device:
            # the map is monotone: each old code moves up past the new
            # values inserted below it, so the device never gathers
            # through `old_to_new`
            thresholds, _ = new_values(old_dict, update_dict)
            rows_w, codes_w = _last_writes(write_ops, write_codes)
            new_codes, valid = be.reencode_resident(
                col.codes, col.valid, thresholds, rows_w, codes_w,
                dels["row"])
        else:
            new_codes = old_to_new[np.asarray(col.codes)].astype(np.int32)
            new_codes, valid = _apply_row_ops(
                new_codes, np.array(col.valid, copy=True), new_dict, mods,
                ins, dels, encode=encode, write_set=(write_ops, write_codes))
            if resident:
                # a resident column's host apply (an insert batch) goes
                # back to the device
                new_codes, valid = jax.device_put((new_codes, valid))

    if cost is not None and m:
        _optimized_apply_cost(cost, on_pim, m, n, k_old, len(new_dict),
                              len(update_dict), col.bit_width, phase=phase)

    return EncodedColumn(codes=new_codes, dictionary=np.asarray(new_dict),
                         valid=valid, version=col.version + 1), (
        "device" if on_device else "host")


def apply_updates_shards(
    col: EncodedColumn,
    updates: np.ndarray,
    cost: CostLog | None = None,
    on_pim: bool = True,
    backend=None,
    staged=None,
    phase: str = "apply",
) -> list[EncodedColumn]:
    """Update application across N analytical islands (row-wise shards).

    The dictionary is replicated across islands, so stages 1-2 (update-
    dictionary sort, dictionary merge, old->new index) run once on the
    inner backend. Stage 3 is island-local: each update is routed to its
    owning shard by row id (`route_updates`), each island re-encodes its
    shard through the shared index and scatters only its own row ops.

    Returns the per-island shard columns, one per island in row order —
    the units the Phase-2 swap installs all-or-none
    (`ConsistencyManager.on_update_shards`). Because the shards partition
    the rows and every island uses the same merged dictionary, their
    concatenation is bit-identical to the unsharded `apply_updates` — that
    equivalence is asserted in tests/test_sharded_backend.py.
    """
    be = get_backend(backend)
    if not isinstance(be, ShardedBackend):
        raise ValueError("apply_updates_shards needs a ShardedBackend "
                         f"(got {getattr(be, 'name', be)!r}); use "
                         "apply_updates for single-replica application")
    inner = be.inner
    old_codes = np.asarray(col.codes)
    old_dict = np.asarray(col.dictionary)
    old_valid = np.asarray(col.valid)
    n, k_old = old_codes.shape[0], old_dict.shape[0]
    mods, ins, dels = _split_ops(updates)
    write_vals = np.concatenate([mods["value"], ins["value"]])
    m = len(updates)

    # Stages 1-2 once on the shared (replicated) dictionary — the same
    # code path as the unsharded apply, so the maps cannot drift apart.
    update_dict, new_dict, encode, old_to_new = (
        staged if staged is not None
        else _merge_dictionary_stages(inner, old_dict, write_vals))

    # Stage 3 per island: route row ops to owning shards over the
    # post-insert row span (inserts extend the last shard). Each island's
    # write set is materialized first so the value->code encodes of ALL
    # islands ride one batched probe launch (encode_values_shards — the
    # hash unit's leading-shard-axis path) instead of one probe per island.
    n_new = max(n, int(ins["row"].max()) + 1) if len(ins) else n
    bounds = shard_bounds(n_new, be.n_shards)
    owner = route_updates(updates, bounds)
    island_ops = []
    for s in range(be.n_shards):
        lo = bounds[s]
        ups_s = updates[owner == s]
        ups_s["row"] = ups_s["row"] - lo  # island-local row ids
        m_s, i_s, d_s = _split_ops(ups_s)
        w_s = _sorted_write_ops(m_s, i_s)
        island_ops.append((m_s, i_s, d_s, w_s))
    write_codes = inner.encode_values_shards(
        encode, [w["value"] for *_, w in island_ops])
    codes_parts, valid_parts = [], []
    for s, ((m_s, i_s, d_s, w_s), wc) in enumerate(zip(island_ops,
                                                       write_codes)):
        lo, hi = bounds[s], bounds[s + 1]
        with span("reencode", n=hi - lo):
            src_lo, src_hi = min(lo, n), min(hi, n)
            codes_s = old_to_new[old_codes[src_lo:src_hi]].astype(np.int32)
            valid_s = np.array(old_valid[src_lo:src_hi], copy=True)
            pad = (hi - lo) - (src_hi - src_lo)
            if pad:  # rows this island gains from inserts
                codes_s = np.concatenate([codes_s, np.zeros(pad, np.int32)])
                valid_s = np.concatenate([valid_s, np.zeros(pad, bool)])
            codes_s, valid_s = _apply_row_ops(codes_s, valid_s, new_dict,
                                              m_s, i_s, d_s, encode=encode,
                                              write_set=(w_s, wc))
        codes_parts.append(codes_s)
        valid_parts.append(valid_s)

    if cost is not None and m:
        _optimized_apply_cost(cost, on_pim, m, n, k_old, len(new_dict),
                              len(update_dict), col.bit_width, phase=phase)

    shared_dict = np.asarray(new_dict)  # one replicated dictionary object
    return [
        EncodedColumn(codes=np.asarray(codes_s), dictionary=shared_dict,
                      valid=np.asarray(valid_s), version=col.version + 1)
        for codes_s, valid_s in zip(codes_parts, valid_parts)
    ]


def apply_updates_islands(
    view: ShardedView,
    updates: np.ndarray,
    cost: CostLog | None = None,
    on_pim: bool = True,
    backend=None,
    staged=None,
    phase: str = "apply",
) -> tuple[ShardedView, str]:
    """Update application on islands that each hold their shard on their
    own device (a `MeshBackend`'s eager plane): the new resident column,
    and where its stage 3 ran.

    Stages 1-2 run once on the host, as the dictionary is replicated,
    giving one threshold vector. Each update goes to its island by row
    (`route_updates`), in local row ids, and one launch runs every
    island's stage 3 on its device (`MeshBackend.reencode_islands`).
    Nothing crosses to the host. Each island's host prep is a
    ``reencode`` span of its rows; the launch leaves in the last one. An
    insert batch, or values past int32, takes the host stage 3
    (`apply_updates_shards`) as on one chip, and its shards go back to
    their devices. Either way the result is the same rows as the
    unsharded `apply_updates`.
    """
    be = get_backend(backend)
    mods, ins, dels = _split_ops(updates)
    if len(ins) or not _fits_int32(mods["value"]):
        shards = apply_updates_shards(host_column(view), updates, cost,
                                      on_pim, be, staged=staged, phase=phase)
        return be.place_shards(shards), "host"
    old_dict = np.asarray(view.dictionary)
    update_dict, new_dict, encode, _ = (
        staged if staged is not None
        else _merge_dictionary_stages(be.inner, old_dict, mods["value"]))
    thresholds, _ = new_values(old_dict, update_dict)
    owner = route_updates(updates, list(view.bounds))
    writes, deletes = [], []
    for s in range(view.n_shards):
        with span("reencode", n=view.sizes[s]):
            ups = updates[owner == s]
            ups["row"] -= view.bounds[s]      # island-local row ids
            m_s, _, d_s = _split_ops(ups)
            w_s = _sorted_write_ops(m_s, ins)
            writes.append(_last_writes(w_s, encode(w_s["value"])))
            deletes.append(d_s["row"])
            if s == view.n_shards - 1:
                codes, valid = be.reencode_islands(view, thresholds, writes,
                                                   deletes)
    if cost is not None and len(updates):
        _optimized_apply_cost(cost, on_pim, len(updates), view.n_rows,
                              len(old_dict), len(new_dict), len(update_dict),
                              view.bit_width, phase=phase)
    return ShardedView(codes=codes, valid=valid,
                       dictionary=np.asarray(new_dict), bounds=view.bounds,
                       version=view.version + 1), "device"


def apply_updates_naive(
    col: EncodedColumn,
    updates: np.ndarray,
    cost: CostLog | None = None,
    phase: str = "apply",
) -> EncodedColumn:
    """The paper's initial algorithm (§5.2), costed as CPU software.

    decompress -> apply -> full sort to rebuild dictionary -> recompress.
    Used as the functional oracle and as the MI baseline's cost generator
    (62.6% of update-application cycles go to (de)compression, Fig. 3).
    """
    old_codes = np.asarray(col.codes)
    old_dict = np.asarray(col.dictionary)
    valid = np.array(col.valid, copy=True)
    n = old_codes.shape[0]
    mods, ins, dels = _split_ops(updates)
    m = len(updates)

    # Step 1: decompress (n random dictionary lookups).
    values = old_dict[old_codes]
    # Step 2: apply updates one by one (vectorized, last-writer-wins).
    if len(ins):
        top = int(ins["row"].max()) + 1
        if top > len(values):
            pad = top - len(values)
            values = np.concatenate([values, np.zeros(pad, dtype=values.dtype)])
            valid = np.concatenate([valid, np.zeros(pad, dtype=bool)])
    write_ops = np.concatenate([mods, ins]) if len(ins) else mods
    if len(write_ops):
        order = np.argsort(write_ops["commit_id"], kind="stable")
        write_ops = write_ops[order]
        values[write_ops["row"]] = write_ops["value"]
        valid[write_ops["row"]] = True
    if len(dels):
        valid[dels["row"]] = False
    # Step 3: rebuild dictionary by sorting the updated column.
    new_dict = np.unique(values)
    # Step 4: recompress via per-entry binary search (logarithmic).
    new_codes = np.searchsorted(new_dict, values).astype(np.int32)

    if cost is not None and m:
        cost.annotate_add(n_applied=int(m))
        k_new = len(new_dict)
        n_tot = len(values)
        n_eff = min(n_tot,
                    max(1, min(m, n_tot // PARTITION_ROWS + 1)) * PARTITION_ROWS)
        # per-partition (de)compression: decompress + full sort + recompress.
        # SIMD-friendly in-cache sort: ~1 cycle/item/pass, log2(P) passes.
        logp = np.log2(max(PARTITION_ROWS, 2))
        cost.add(
            phase=phase, island="txn", resource="cpu",
            cycles=n_eff * 3.0                                       # decompress
            + m * CPU_CYCLES_PER_SCAN_ITEM                           # apply
            + n_eff * logp * 1.0                                     # sort passes
            + n_eff * 3.0,                                           # recompress
            bytes_offchip=(
                n_eff * VALUE_BYTES * 2           # decode read+write
                + n_eff * VALUE_BYTES * 2.0       # sort passes (out-of-cache)
                + n_eff * VALUE_BYTES * 1.5       # binary-search traffic
            ),
        )

    return EncodedColumn(
        codes=np.asarray(new_codes),
        dictionary=np.asarray(new_dict.astype(old_dict.dtype)),
        valid=np.asarray(valid),
        version=col.version + 1,
    )


# ---------------------------------------------------------------------------
# Delta-store update plane: append-only overlay + background compaction
# ---------------------------------------------------------------------------

def delta_eligible(updates: np.ndarray, n_base: int) -> bool:
    """A batch can ride the delta overlay iff it only modifies/deletes
    EXISTING base rows. Inserts (op 2) and writes past the base row count
    would change the column length, which the overlay algebra deliberately
    does not model — those batches fall back to compact-then-eager-apply
    (session workloads never emit them)."""
    if len(updates) == 0:
        return True
    if np.any(updates["op"] == 2):
        return False
    return int(updates["row"].max()) < n_base


def apply_updates_delta(
    col: EncodedColumn,
    delta: ColumnDelta,
    updates: np.ndarray,
    cost: CostLog | None = None,
    on_pim: bool = True,
    backend=None,
) -> ColumnDelta:
    """Append a shipped update batch to the column's delta overlay.

    The delta-store fast path: instead of the two-stage rebuild
    (`apply_updates` — dictionary merge + full soft-partition re-encode),
    the batch collapses to one overlay entry per touched row
    (last-writer-wins, reproducing `_apply_row_ops`'s writes-then-deletes
    batch semantics) and merges into the existing sorted overlay as a
    sorted-run merge keyed by row id (merge unit; the same int64-lane
    `kernels/merge_runs` machinery the dictionary merge rides). Work is
    O(m + d), never O(n) — the base column is untouched, which is exactly
    why append visibility is cheap and freshness improves at high commit
    rates. Scans see the batch via the query-time base+overlay merge
    (engine.run_query_group_dsm) and compaction later folds the overlay
    back into the base (`compaction_entries` -> the standard apply).

    Requires `delta_eligible(updates, delta.n_base)`; raises ValueError
    otherwise. Returns the NEW overlay (functional update — the caller
    swaps the pointer, mirroring the Phase-2 contract).
    """
    if not delta_eligible(updates, delta.n_base):
        raise ValueError(
            "update batch has inserts or rows past the overlay's base row "
            "count; compact the overlay and use the eager apply instead")
    m = len(updates)
    if m == 0:
        return delta
    be = get_backend(backend)
    inner = be.inner if isinstance(be, ShardedBackend) else be

    mods = updates[updates["op"] == 1]
    dels = updates[updates["op"] == 3]
    # commit order within the batch (ship buffers are commit-ordered per
    # column already; sort defensively, same as _sorted_write_ops)
    if len(mods):
        mods = mods[np.argsort(mods["commit_id"], kind="stable")]
    if len(dels):
        dels = dels[np.argsort(dels["commit_id"], kind="stable")]

    rows_b = np.unique(np.concatenate([mods["row"], dels["row"]])
                       ).astype(np.int64)
    d_batch = len(rows_b)
    if d_batch == 0:  # read-only batch: state-neutral, still priced below
        new = ColumnDelta(rows=delta.rows, values=delta.values,
                          valid=delta.valid, cids=delta.cids,
                          n_base=delta.n_base,
                          n_entries=delta.n_entries + m)
        _delta_append_cost(cost, on_pim, m, delta.n_overlay, 0,
                           new.n_overlay)
        return new

    # Per-row batch state, matching the eager batch semantics exactly:
    # ALL writes land in commit order (last one wins), then deletes clear
    # validity — a written+deleted row keeps its written value.
    has_w = np.zeros(d_batch, dtype=bool)
    last_val = np.zeros(d_batch, dtype=np.int32)
    if len(mods):
        wi = np.searchsorted(rows_b, mods["row"].astype(np.int64))
        has_w[wi] = True
        last_val[wi] = mods["value"]          # in-order scatter: last wins
    has_d = np.zeros(d_batch, dtype=bool)
    if len(dels):
        has_d[np.searchsorted(rows_b, dels["row"].astype(np.int64))] = True
    valid_b = has_w & ~has_d
    # delete-only rows carry the row's CURRENT effective value (the eager
    # path keeps a deleted row's code, and f-selected aggregates still read
    # it) — previous overlay value if the row is overlayed, else base value
    value_b = last_val.copy()
    carry = ~has_w
    if carry.any():
        rows_c = rows_b[carry]
        vals_c = np.asarray(col.dictionary)[
            np.asarray(col.codes)[rows_c]].astype(np.int32)
        if delta.n_overlay:
            oi = np.searchsorted(delta.rows, rows_c)
            oic = np.minimum(oi, delta.n_overlay - 1)
            hit = delta.rows[oic] == rows_c
            vals_c = np.where(hit, delta.values[oic], vals_c)
        value_b[carry] = vals_c
    cid_b = np.zeros(d_batch, dtype=np.int64)
    touch = np.concatenate([mods, dels]) if len(dels) else mods
    if len(touch):
        touch = touch[np.argsort(touch["commit_id"], kind="stable")]
        cid_b[np.searchsorted(rows_b, touch["row"].astype(np.int64))] = \
            touch["commit_id"]                # in-order scatter: latest wins

    # Merge old overlay + batch rows (sorted-run merge on the merge unit
    # when both runs exist); normalize to keep-LAST per key with the batch
    # winning, independent of the merge mode's tie order.
    d_old = delta.n_overlay
    if d_old == 0:
        keys_sorted, sel = rows_b, np.arange(d_batch, dtype=np.int64)
    else:
        if isinstance(inner, PallasBackend) and d_batch:
            merged_keys, src = merge_sorted_runs([delta.rows, rows_b])
            keys, src = np.asarray(merged_keys), np.asarray(src)
            live = src >= 0           # defensive: sentinel-trimmed already
            keys, src = keys[live], src[live]
        else:
            keys = np.concatenate([delta.rows, rows_b])
            src = np.arange(d_old + d_batch, dtype=np.int64)
        order = np.lexsort((src, keys))
        keys_sorted, sel = keys[order], src[order]
        keep = np.append(keys_sorted[1:] != keys_sorted[:-1], True)
        keys_sorted, sel = keys_sorted[keep], sel[keep]
    cat_vals = np.concatenate([delta.values, value_b])
    cat_valid = np.concatenate([delta.valid, valid_b])
    cat_cids = np.concatenate([delta.cids, cid_b])
    new = ColumnDelta(rows=keys_sorted.astype(np.int64),
                      values=cat_vals[sel], valid=cat_valid[sel],
                      cids=cat_cids[sel], n_base=delta.n_base,
                      n_entries=delta.n_entries + m)
    _delta_append_cost(cost, on_pim, m, d_old, d_batch, new.n_overlay)
    return new


def _delta_append_cost(cost: CostLog | None, on_pim: bool, m: int,
                       d_old: int, d_batch: int, d_new: int) -> None:
    """Cost events for one overlay append, priced as the hardware delta
    plane maintains it: collapse the batch to per-row state (sorter),
    write the collapsed run into the overlay's run list (copy unit), and
    the amortized run-list bookkeeping (merge unit — total merge work over
    an overlay's lifetime is O(entries appended), charged incrementally
    per batch). Crucially there is NO O(n) re-encode term and NO O(d_old)
    overlay-rewrite term: appends stay O(batch), which is the whole
    freshness win over `apply_updates`. The deferred work does not vanish
    — every scan pays the base+overlay merge (engine's correction pass)
    and the full fold into the base is paid at compaction, so the model
    stays honest about where the delta plane moves the cycles."""
    if cost is None or m == 0:
        return
    cost.annotate_add(n_applied=int(m))
    if on_pim:
        cost.add(phase="apply", island="ana", resource="sorter", items=m)
        cost.add(phase="apply", island="ana", resource="merge",
                 items=d_batch, bytes_local=d_batch * DELTA_ENTRY_BYTES)
        cost.add(phase="apply", island="ana", resource="copy",
                 bytes_local=2 * d_batch * DELTA_ENTRY_BYTES)
    else:
        cost.add(
            phase="apply", island="txn", resource="cpu",
            cycles=m * np.log2(max(m, 2)) * CPU_CYCLES_PER_CMP
            + m * CPU_CYCLES_PER_SCAN_ITEM
            + m * CPU_CYCLES_PER_LOOKUP,
            bytes_offchip=2 * d_batch * DELTA_ENTRY_BYTES,
        )


def compaction_entries(delta: ColumnDelta, col_id: int = 0) -> np.ndarray:
    """Synthesize the update batch that folds an overlay into the base.

    One write per overlay row (every row carries a defined value — see
    `ColumnDelta.values` — so a deleted row's last value lands in the base
    codes exactly as the eager path would have left it) plus a delete for
    each invalid row, all stamped with the overlay's stored commit ids and
    sorted back into commit order. Feeding this through the standard
    `apply_updates` family reproduces the eager end state bit-for-bit,
    modulo a possibly SMALLER dictionary (the eager path keeps overwritten
    values in its dictionary; both dictionaries are sorted supersets of
    the live values, so every code range maps to the same value range and
    answers are unchanged).
    """
    d = delta.n_overlay
    writes = np.zeros(d, dtype=UPDATE_DTYPE)
    writes["commit_id"] = delta.cids
    writes["op"] = 1
    writes["value"] = delta.values
    writes["row"] = delta.rows
    writes["col"] = col_id
    invalid = ~delta.valid
    dels = np.zeros(int(invalid.sum()), dtype=UPDATE_DTYPE)
    dels["commit_id"] = delta.cids[invalid]
    dels["op"] = 3
    dels["value"] = delta.values[invalid]
    dels["row"] = delta.rows[invalid]
    dels["col"] = col_id
    cat = np.concatenate([writes, dels])
    # stable: a row's delete sorts after its equal-cid write, reproducing
    # the eager writes-then-deletes batch order
    return cat[np.argsort(cat["commit_id"], kind="stable")]
