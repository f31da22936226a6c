"""Public wrappers for the fused dictionary-encoded scan.

Execution mode (``common.kernel_mode``): the Pallas kernels run compiled on
real accelerators or in interpret mode when forced; on CPU the default is
the jitted jax-numpy lowering (``lowered.py``), which produces the *same*
per-block split-accumulator partials — the host reassembly below is shared
by both paths and the results are bit-identical.

Dispatch-overhead note (the CPU fast path's whole point): the lowered
entry points take the RAW arrays and pad *inside* the traced call, and the
query bounds stay host numpy (jit converts an np argument cheaper than an
eager ``jnp.asarray``) — so a warm scan costs one jitted dispatch plus the
host reassembly, no eager device ops. Shapes stay trace-stable because the
dictionary and query-count axes are pow2-bucketed here on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (ISLAND_AXIS, island_spec,
                                        replicated_spec)
from repro.kernels.bitonic_sort.bitonic_sort import (bitonic_merge_rows,
                                                     bitonic_sort_rows)
from repro.kernels.common import (LANES, count_decode, donation_enabled,
                                  instrumented_jit, kernel_mode,
                                  lanes_to_int64, next_pow2, psum_split16,
                                  width_bucket)
from repro.kernels.dict_ops.dict_ops import (DICT_SELECT_MAX, decode_path,
                                             scan_exact_decoded,
                                             scan_filter_agg_exact_kernel,
                                             scan_filter_agg_sharded_kernel,
                                             scan_values_agg_exact_kernel)
from repro.kernels.dict_ops.lowered import (apply_pipeline_lowered,
                                            apply_pipeline_lowered_donated,
                                            pad_rows_sharded,
                                            scan_exact_lowered,
                                            scan_exact_sharded_lowered,
                                            scan_exact_sharded_partials,
                                            scan_group_lowered,
                                            scan_group_sharded_lowered,
                                            scan_values_delta_lowered,
                                            scan_values_lowered)
from repro.kernels.dict_ops.ref import (scan_filter_agg_batch_ref,
                                        scan_filter_agg_ref,
                                        scan_filter_agg_sharded_ref,
                                        scan_values_agg_ref)

_I32_MAX = np.iinfo(np.int32).max
# The least width a wider dictionary operand is padded to. Every program
# that decodes through a dictionary compiles once per padded width, a few
# seconds each on the chip's host, so a column whose dictionary grows
# through several powers of two inside a served window would compile its
# scans each time; past `DICT_SELECT_MAX` it does so once more at most,
# at 2^17 entries. The pad is a host array of 512 KiB, sent with each
# scan.
DICT_PAD_MIN = 1 << 17


def pad_dictionary_pow2(dictionary):
    """Pad a dictionary to the next power of two up to `DICT_SELECT_MAX`
    entries, and to at least `DICT_PAD_MIN` past it, so growing
    dictionaries reuse compiled shapes; padded entries are never addressed
    by a code. Type-preserving: host numpy stays host numpy (no eager
    device op)."""
    k = dictionary.shape[0]
    w = next_pow2(k)
    kpad = (w if w <= DICT_SELECT_MAX else max(w, DICT_PAD_MIN)) - k
    if not kpad:
        return dictionary
    if isinstance(dictionary, np.ndarray):
        # hot path: a plain alloc+copy beats np.pad's generic machinery
        out = np.zeros(k + kpad, dtype=dictionary.dtype)
        out[:k] = dictionary
        return out
    return jnp.pad(dictionary, (0, kpad))


def pad_bounds_pow2(bounds) -> np.ndarray:
    """(Q, 2) int32 code bounds padded to a pow2 query count with empty
    ranges — bounding the number of distinct compiled shapes. Returned as
    host numpy; the jitted callee converts it on dispatch."""
    nq = len(bounds)
    barr = np.zeros((next_pow2(nq), 2), dtype=np.int32)
    barr[:nq] = np.asarray(bounds, dtype=np.int32).reshape(-1, 2)
    return barr


def assemble_exact(lo16, hi16, cnt, neg, axis):
    """Reassemble exact int64 (sums, counts) from split-16-bit partials.

    sum(u32(v)) - 2^32 * #negatives == exact signed sum; `axis` is the
    per-block partial axis being reduced (0 for (nb, Q) partials, 1 for
    (n_shards, nb, Q)).
    """
    lo64 = np.asarray(lo16).astype(np.int64).sum(axis=axis)
    hi64 = np.asarray(hi16).astype(np.int64).sum(axis=axis)
    counts = np.asarray(cnt).astype(np.int64).sum(axis=axis)
    negs = np.asarray(neg).astype(np.int64).sum(axis=axis)
    sums = lo64 + (hi64 << np.int64(16)) - (negs << np.int64(32))
    return sums, counts


def count_decodes(*dictionaries, hist: bool = False) -> None:
    """Count one decode per (padded) dictionary, by the path
    `dict_ops.decode_path` gives a program with (``hist``) or without the
    histogram branch."""
    for d in dictionaries:
        count_decode(decode_path(d.shape[0], hist))


def live_length(dictionary) -> np.ndarray:
    """The (1,) int32 live length of an unpadded dictionary, the
    histogram's scalar operand (`dict_ops._hist_counts`)."""
    return np.asarray([dictionary.shape[0]], dtype=np.int32)


def exact_totals(parts, dictionary):
    """Exact int64 (sums, counts), one per padded query, of the outputs of
    `dict_ops.scan_filter_agg_exact_kernel`: split-sum partials
    (`assemble_exact`), or a 1-tuple of code counts, dotted in int64 with
    the unpadded ``dictionary`` (no code reaches past its length)."""
    if len(parts) != 1:
        return assemble_exact(*parts, axis=0)
    (hist,) = parts
    k = dictionary.shape[0]
    counts = np.asarray(hist).reshape(hist.shape[0], -1)[:, :k]
    counts = counts.astype(np.int64)
    return (counts @ np.asarray(dictionary).astype(np.int64),
            counts.sum(axis=1))


def scan_exact_dispatch(fcodes, acodes, valid, dictionary, bounds,
                        block: int, live):
    """Mode-dispatched exact scan over RAW (unpadded) flat columns, its
    decode counted: the outputs `scan_filter_agg_exact_kernel` gives for
    its decode path (the lowered path's partials are the kernel's).
    `dictionary` must be pow2-padded, `bounds` a host (pow2(Q), 2) int32
    array, ``live`` the dictionary's `live_length`."""
    mode = kernel_mode()
    count_decodes(dictionary, hist=(mode != "lowered"))
    if mode == "lowered":
        return scan_exact_lowered(fcodes, acodes, valid, dictionary, bounds,
                                  block=block)
    return scan_filter_agg_exact_kernel(fcodes, acodes, valid, dictionary,
                                        bounds, live, block=block,
                                        interpret=(mode == "interpret"))


def scan_exact_sharded_dispatch(fcodes, acodes, valid, dictionary, bounds,
                                block: int):
    """Mode-dispatched stacked-shard scan over RAW (n_shards, width) arrays:
    (n_shards, nb, Q) partials. Padding contract as scan_exact_dispatch
    (stacked padding carries valid=0, the scan identity)."""
    mode = kernel_mode()
    if mode == "lowered":
        return scan_exact_sharded_lowered(fcodes, acodes, valid, dictionary,
                                          bounds, block=block)
    return scan_filter_agg_sharded_kernel(fcodes, acodes, valid, dictionary,
                                          bounds, block=block,
                                          interpret=(mode == "interpret"))


def scan_filter_agg(fcodes, acodes, valid, dictionary, code_lo, code_hi,
                    use_pallas: bool = True, block: int = 4096,
                    exact: bool = False):
    """sum(dict[acodes]) and count over rows with code_lo <= fcodes < code_hi.

    exact=True returns exact python ints (the execution-backend path); the
    default returns the sum as float32, cast from the same exact scan.
    """
    if not use_pallas and not exact:
        return scan_filter_agg_ref(fcodes, acodes, valid, dictionary,
                                   code_lo, code_hi)
    [(s, c)] = scan_filter_agg_batch(fcodes, acodes, valid, dictionary,
                                     [(code_lo, code_hi)],
                                     use_pallas=use_pallas, block=block)
    return (s, c) if exact else (np.float32(s), c)


def scan_filter_agg_batch(fcodes, acodes, valid, dictionary, bounds,
                          use_pallas: bool = True, block: int = 4096):
    """One fused pass answering Q code-range queries over the same columns.

    bounds: sequence of (code_lo, code_hi). Returns [(sum, count), ...] as
    exact python ints — bit-identical to the numpy engine's int64 aggregate.
    """
    if not use_pallas:
        return scan_filter_agg_batch_ref(fcodes, acodes, valid, dictionary,
                                         bounds)
    (n,) = fcodes.shape
    if n == 0 or not len(bounds):
        return [(0, 0) for _ in bounds]
    nq = len(bounds)
    parts = scan_exact_dispatch(
        fcodes, acodes, valid, pad_dictionary_pow2(dictionary),
        pad_bounds_pow2(bounds), block, live_length(dictionary))
    sums, counts = exact_totals(parts, dictionary)
    return [(int(s), int(c)) for s, c in zip(sums[:nq], counts[:nq])]


def scan_filter_agg_sharded(fcodes, acodes, valid, dictionary, bounds,
                            use_pallas: bool = True, block: int = 4096):
    """All islands' fused scans in ONE launch over a leading shard axis.

    fcodes/acodes/valid: (n_shards, width) stacked resident shards (padded
    slots must carry valid=0 — see dsm.ShardedView). bounds: Q (code_lo,
    code_hi) predicates shared by every island. Returns per-island exact
    partials: [[(sum, count), ...Q] ...n_shards] as python ints,
    bit-identical to running the unsharded scan per shard.
    """
    if not use_pallas:
        return scan_filter_agg_sharded_ref(fcodes, acodes, valid, dictionary,
                                           bounds)
    n_shards, width = fcodes.shape
    nq = len(bounds)
    if width == 0 or nq == 0:
        return [[(0, 0)] * nq for _ in range(n_shards)]
    # bucket the block to the (pow2) shard width so small shards don't pad
    # a 4096-wide tile each
    block = min(block, next_pow2(width))
    dpad = pad_dictionary_pow2(dictionary)
    count_decodes(dpad)
    lo16, hi16, cnt, neg = scan_exact_sharded_dispatch(
        fcodes, acodes, valid, dpad, pad_bounds_pow2(bounds), block)
    sums, counts = assemble_exact(lo16, hi16, cnt, neg, axis=1)
    return [[(int(sums[s, q]), int(counts[s, q])) for q in range(nq)]
            for s in range(n_shards)]


def scan_values_agg(fvals, avals, valid, bounds, use_pallas: bool = True,
                    block: int = 4096):
    """One fused pass answering Q INCLUSIVE value-range queries over raw
    (decoded) overlay rows — the delta-store correction scan.

    fvals/avals: int32 raw values (no dictionary); valid: overlay validity.
    Returns [(sum, count), ...] exact python ints. Overlay lengths vary per
    query group, so rows are pow2-bucketed and padded HERE on the host
    (valid=0 pad is the scan identity for any pad value of fvals/avals) —
    keeping the traced shape count logarithmic in overlay size.
    """
    if not use_pallas:
        return scan_values_agg_ref(fvals, avals, valid, bounds)
    n = int(np.asarray(fvals).shape[0])
    nq = len(bounds)
    if n == 0 or nq == 0:
        return [(0, 0) for _ in bounds]
    block = min(block, next_pow2(n))
    pad = (-n) % block
    f = np.asarray(fvals, dtype=np.int32)
    a = np.asarray(avals, dtype=np.int32)
    v = np.asarray(valid).astype(np.int32)
    if pad:
        f = np.pad(f, (0, pad))
        a = np.pad(a, (0, pad))
        v = np.pad(v, (0, pad))
    barr = pad_bounds_pow2(bounds)
    mode = kernel_mode()
    if mode == "lowered":
        parts = scan_values_lowered(f, a, v, barr, block=block)
    else:
        parts = scan_values_agg_exact_kernel(
            jnp.asarray(f), jnp.asarray(a), jnp.asarray(v),
            jnp.asarray(barr), block=block, interpret=(mode == "interpret"))
    sums, counts = assemble_exact(*parts, axis=0)
    return [(int(s), int(c)) for s, c in zip(sums[:nq], counts[:nq])]


# ---------------------------------------------------------------------------
# Fused pipelines (PR 9): single-launch query groups and ship-batch apply
# ---------------------------------------------------------------------------
#
# Pallas-mode fused bodies: same composition as the lowered twins in
# lowered.py, but each constituent scan runs through its pallas_call kernel
# inside ONE outer traced program (the established hash_probe join-scan
# idiom). Only the apply pipeline has a donated twin (its sorted values
# alias the value stack) — selected via common.donation_enabled(); see the
# donation-policy note in kernels/common.py.

def _scan_group_kernel_body(fcodes, acodes, valid, dictionary, bounds, corr,
                            vbounds, block, cblock, interpret):
    base = scan_exact_decoded(fcodes, acodes, valid, dictionary, bounds,
                              block, interpret)
    eff = scan_values_agg_exact_kernel(corr[0], corr[1], corr[2], vbounds,
                                       block=cblock, interpret=interpret)
    neg = scan_values_agg_exact_kernel(corr[3], corr[4], corr[5], vbounds,
                                       block=cblock, interpret=interpret)
    return base + eff + neg


def _scan_group_sharded_kernel_body(fcodes, acodes, valid, dictionary,
                                    bounds, corr, vbounds, block, cblock,
                                    interpret):
    base = scan_filter_agg_sharded_kernel(fcodes, acodes, valid, dictionary,
                                          bounds, block=block,
                                          interpret=interpret)
    eff = scan_values_agg_exact_kernel(corr[0], corr[1], corr[2], vbounds,
                                       block=cblock, interpret=interpret)
    neg = scan_values_agg_exact_kernel(corr[3], corr[4], corr[5], vbounds,
                                       block=cblock, interpret=interpret)
    return base + eff + neg


def _scan_values_delta_kernel_body(corr, vbounds, cblock, interpret):
    eff = scan_values_agg_exact_kernel(corr[0], corr[1], corr[2], vbounds,
                                       block=cblock, interpret=interpret)
    neg = scan_values_agg_exact_kernel(corr[3], corr[4], corr[5], vbounds,
                                       block=cblock, interpret=interpret)
    return eff + neg


def _apply_pipeline_kernel_body(old, vals, interpret):
    rows, w_old = old.shape
    w_val = vals.shape[1]
    svals = bitonic_sort_rows(vals, block_rows=8, interpret=interpret)
    # the merge kernel runs at >= one lane-width; the extra width widens
    # the all-sentinel gap, which keeps each row bitonic
    w_merge = max(LANES, next_pow2(w_old + w_val))
    parts = [old]
    gap = w_merge - w_old - w_val
    if gap:
        parts.append(jnp.full((rows, gap), _I32_MAX, dtype=old.dtype))
    parts.append(svals[:, ::-1])
    merged = bitonic_merge_rows(jnp.concatenate(parts, axis=1),
                                block_rows=8, interpret=interpret)
    return svals, merged


_GROUP_STATICS = ("block", "cblock", "interpret")
_scan_group_kernel = functools.partial(
    instrumented_jit, static_argnames=_GROUP_STATICS,
    name="scan_group_kernel")(_scan_group_kernel_body)
_scan_group_sharded_kernel = functools.partial(
    instrumented_jit, static_argnames=_GROUP_STATICS,
    name="scan_group_sharded_kernel")(_scan_group_sharded_kernel_body)
_scan_values_delta_kernel = functools.partial(
    instrumented_jit, static_argnames=("cblock", "interpret"),
    name="scan_values_delta_kernel")(_scan_values_delta_kernel_body)
_apply_pipeline_kernel = functools.partial(
    instrumented_jit, static_argnames=("interpret",),
    name="apply_pipeline_kernel")(_apply_pipeline_kernel_body)
_apply_pipeline_kernel_donated = functools.partial(
    instrumented_jit, static_argnames=("interpret",), donate_argnums=(1,),
    name="apply_pipeline_kernel")(_apply_pipeline_kernel_body)


def _padded_corr(corr):
    """Host pow2-bucket pad of a (6, nr) int32 correction stack.

    Overlay sizes vary per round, so padding happens on the host with
    `width_bucket` (floor 8) to bound the traced shapes; the padded lanes
    carry valid=0, the scan identity. Returns (stack, cblock).
    """
    corr = (np.zeros((6, 8), dtype=np.int32) if corr is None
            else np.asarray(corr, dtype=np.int32))
    nr = corr.shape[1]
    w = width_bucket(nr)
    if w != nr:
        corr = np.pad(corr, ((0, 0), (0, w - nr)))
    return corr, min(4096, w)


def scan_filter_agg_group(fcodes, acodes, valid, dictionary, code_bounds,
                          corr, vbounds, block: int = 4096):
    """One no-join query group — base scan PLUS delta correction — in ONE
    traced launch.

    code_bounds: Q EXCLUSIVE code ranges for the base columns; vbounds: the
    same Q predicates as INCLUSIVE raw-value ranges for the overlay
    correction scans; corr: (6, nr) int32 stack of [fv_eff, av_eff,
    valid_eff, fv_base, av_base, valid_base] overlay rows (None = no
    overlay). Returns [(sum, count)] exact python ints with the correction
    folded: base + effective-state - base-state, bit-identical to the
    compositional scan_filter_agg_batch + two scan_values_agg passes.
    """
    (n,) = fcodes.shape
    nq = len(code_bounds)
    if nq == 0:
        return []
    if n == 0:
        return [(0, 0)] * nq
    cstack, cblock = _padded_corr(corr)
    barr = pad_bounds_pow2(code_bounds)
    varr = pad_bounds_pow2(vbounds)
    dpad = pad_dictionary_pow2(dictionary)
    count_decodes(dpad)
    mode = kernel_mode()
    if mode == "lowered":
        parts = scan_group_lowered(fcodes, acodes, valid, dpad, barr,
                                   cstack, varr, block=block, cblock=cblock)
    else:
        parts = _scan_group_kernel(fcodes, acodes, valid, dpad, barr,
                                   cstack, varr, block=block, cblock=cblock,
                                   interpret=(mode == "interpret"))
    bs, bc = assemble_exact(*parts[0:4], axis=0)
    es, ec = assemble_exact(*parts[4:8], axis=0)
    gs, gc = assemble_exact(*parts[8:12], axis=0)
    return [(int(bs[q] + es[q] - gs[q]), int(bc[q] + ec[q] - gc[q]))
            for q in range(nq)]


def scan_filter_agg_group_sharded(fcodes, acodes, valid, dictionary,
                                  code_bounds, corr, vbounds,
                                  block: int = 4096):
    """Sharded sibling of `scan_filter_agg_group`: the base scan runs over
    the stacked (n_shards, width) resident shards, the correction scans
    over the flat (global) overlay stack, all in ONE launch. Returns the
    already-reduced [(sum, count)] — cross-shard totals with the
    correction folded."""
    n_shards, width = fcodes.shape
    nq = len(code_bounds)
    if nq == 0:
        return []
    if width == 0:
        return [(0, 0)] * nq
    block = min(block, next_pow2(width))
    cstack, cblock = _padded_corr(corr)
    barr = pad_bounds_pow2(code_bounds)
    varr = pad_bounds_pow2(vbounds)
    dpad = pad_dictionary_pow2(dictionary)
    count_decodes(dpad)
    mode = kernel_mode()
    if mode == "lowered":
        parts = scan_group_sharded_lowered(fcodes, acodes, valid, dpad,
                                           barr, cstack, varr, block=block,
                                           cblock=cblock)
    else:
        parts = _scan_group_sharded_kernel(fcodes, acodes, valid, dpad,
                                           barr, cstack, varr, block=block,
                                           cblock=cblock,
                                           interpret=(mode == "interpret"))
    bs, bc = assemble_exact(*parts[0:4], axis=1)    # (n_shards, Q)
    es, ec = assemble_exact(*parts[4:8], axis=0)    # (Q,)
    gs, gc = assemble_exact(*parts[8:12], axis=0)
    sums = bs.sum(axis=0) + es - gs
    counts = bc.sum(axis=0) + ec - gc
    return [(int(sums[q]), int(counts[q])) for q in range(nq)]


def scan_values_delta(corr, vbounds, use_pallas: bool = True):
    """Effective-minus-base correction scan of one (6, nr) overlay stack in
    ONE launch: returns [(d_sum, d_count)] — the per-query aggregate deltas
    the engine folds into a base scan. Bit-identical to two
    `scan_values_agg` passes subtracted on the host."""
    nq = len(vbounds)
    if nq == 0:
        return []
    if not use_pallas:
        eff = scan_values_agg_ref(corr[0], corr[1], corr[2], vbounds)
        neg = scan_values_agg_ref(corr[3], corr[4], corr[5], vbounds)
        return [(e[0] - b[0], e[1] - b[1]) for e, b in zip(eff, neg)]
    cstack, cblock = _padded_corr(corr)
    varr = pad_bounds_pow2(vbounds)
    mode = kernel_mode()
    if mode == "lowered":
        parts = scan_values_delta_lowered(cstack, varr, cblock=cblock)
    else:
        parts = _scan_values_delta_kernel(cstack, varr, cblock=cblock,
                                          interpret=(mode == "interpret"))
    es, ec = assemble_exact(*parts[0:4], axis=0)
    gs, gc = assemble_exact(*parts[4:8], axis=0)
    return [(int(es[q] - gs[q]), int(ec[q] - gc[q])) for q in range(nq)]


def apply_pipeline_batch(old_rows, val_rows):
    """Fused ship-batch dictionary pipeline: ONE launch for a whole batch.

    old_rows: (rows, w_old) int32 — each row one column's OLD dictionary,
    sorted ascending, int32.max sentinel pad. val_rows: (rows, w_val) raw
    update values, sentinel pad. The two widths are independent pow2
    buckets (callers use `common.width_bucket`), so the sort network runs
    at the (typically much smaller) value width instead of being dragged
    up to the dictionary width. Per row: bitonic-sort the values, then
    half-cleaner-merge them with the old dictionary (ascending old ++
    sentinel gap ++ reversed sorted values is bitonic at
    next_pow2(w_old + w_val)). Returns host (sorted_vals (rows, w_val),
    merged (rows, next_pow2(w_old + w_val))); sentinels sort to the
    tails, callers slice real entries by length. Sentinel-valued REAL
    entries are the caller's problem: columns whose values reach
    int32.max must take the compositional fallback.
    """
    rows, _ = old_rows.shape
    mode = kernel_mode()
    if mode == "lowered":
        fn = (apply_pipeline_lowered_donated if donation_enabled()
              else apply_pipeline_lowered)
        svals, merged = fn(old_rows, val_rows)
    else:
        pad = (-rows) % 8      # pallas row tiling; all-sentinel pad rows
        old, vals = old_rows, val_rows
        if pad:
            old = np.pad(old, ((0, pad), (0, 0)), constant_values=_I32_MAX)
            vals = np.pad(vals, ((0, pad), (0, 0)), constant_values=_I32_MAX)
        fn = (_apply_pipeline_kernel_donated if donation_enabled()
              else _apply_pipeline_kernel)
        svals, merged = fn(old, vals, interpret=(mode == "interpret"))
        svals, merged = svals[:rows], merged[:rows]
    return np.asarray(svals), np.asarray(merged)


# ---------------------------------------------------------------------------
# Mesh placement: one shard_map launch, per-island kernels, psum reduction
# ---------------------------------------------------------------------------

def assemble_psum_lanes(lanes):
    """Reassemble exact int64 (sums, counts) from mesh-psum'd lane pairs.

    `lanes` is the 8-tuple a mesh scan returns: each of the four
    split-accumulator components (lo16, hi16, cnt, neg) psum'd across the
    island axis as a `common.psum_split16` (lo, hi) lane pair of shape
    (nb, Q). Recombining the lanes into int64 and then reducing the block
    axis is the same math as `assemble_exact` with the cross-island sum
    folded in — bit-identical by integer associativity.
    """
    lo16, hi16, cnt, neg = (lanes_to_int64(lanes[i], lanes[i + 1]).sum(axis=0)
                            for i in range(0, 8, 2))
    sums = lo16 + (hi16 << np.int64(16)) - (neg << np.int64(32))
    return sums, cnt


@functools.lru_cache(maxsize=None)
def _mesh_scan_call(mesh, block: int, mode: str):
    """Build (and cache) the jitted shard_map scan for one (mesh, block,
    mode) combination. Inside the map each island device sees its own
    (1, width) resident shard; the dictionary and bounds ride in
    replicated. The per-block partials are psum'd over ``ISLAND_AXIS`` as
    16-bit lanes (see `common.psum_split16`), so the launch's outputs are
    already cross-island totals — O(1) host work regardless of islands.
    """
    def scan_exact_mesh(fcodes, acodes, valid, dictionary, bounds):
        if mode == "lowered":
            fc, ac, v = pad_rows_sharded(fcodes, acodes, valid, block)
            parts = scan_exact_sharded_partials(fc, ac, v, dictionary,
                                                bounds, block)
        else:
            parts = scan_filter_agg_sharded_kernel(
                fcodes, acodes, valid, dictionary, bounds, block=block,
                interpret=(mode == "interpret"))
        out = []
        for p in parts:          # local (1, nb, Q) -> psum'd (nb, Q) lanes
            out.extend(psum_split16(p[0], ISLAND_AXIS))
        return tuple(out)

    smapped = jax.shard_map(
        scan_exact_mesh, mesh=mesh,
        in_specs=(island_spec(), island_spec(), island_spec(),
                  replicated_spec(), replicated_spec()),
        out_specs=(P(None, None),) * 8,
        check_vma=False)  # pallas_call has no replication rule
    return instrumented_jit(smapped, name="scan_exact_mesh")


def scan_filter_agg_mesh(fcodes, acodes, valid, dictionary, bounds, mesh,
                         block: int = 4096):
    """Every island's fused scan in ONE launch on its OWN device.

    The mesh-placement sibling of `scan_filter_agg_sharded`: arrays are the
    same stacked (n_shards, width) resident shards, but laid one island per
    device of `mesh` (see ``distributed.sharding``), and the cross-island
    reduction happens ON the mesh as an integer psum instead of on the
    host. Returns the already-reduced ``[(sum, count)] * Q`` exact python
    ints — bit-identical to reducing the stacked tier's per-island partials.
    """
    n_shards, width = fcodes.shape
    nq = len(bounds)
    if width == 0 or nq == 0:
        return [(0, 0)] * nq
    block = min(block, next_pow2(width))
    dpad = pad_dictionary_pow2(dictionary)
    count_decodes(dpad)
    lanes = _mesh_scan_call(mesh, block, kernel_mode())(
        fcodes, acodes, valid, dpad, pad_bounds_pow2(bounds))
    sums, counts = assemble_psum_lanes(lanes)
    return [(int(sums[q]), int(counts[q])) for q in range(nq)]
