"""Public wrappers: build (host-side, data-dependent) + probe (kernel),
plus the fused join-group scan.

The join-group scan is the device-side replacement for the engine's old
per-query host glue (filter mask -> bincount -> histogram dot): a
self-join's contribution is ``sum_r mask_q[r] * jvalid[r] *
rcount[jcodes[r]]`` — exactly the fused exact-scan structure with the
build side's per-dictionary-value histogram (``rcount``) standing in for
the dictionary. Both the aggregate scan and the join scan of a query
group therefore ride ONE traced call (`scan_filter_agg_join`), and the
sharded variant runs every island in the same launch. ``rcount`` entries
are non-negative row counts (< 2^31), so the split accumulator reassembles
the exact int64 join count just like the aggregate path.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (ISLAND_AXIS, island_spec,
                                        replicated_spec)
from repro.kernels.common import (instrumented_jit, kernel_mode, next_pow2,
                                  psum_split16)
from repro.kernels.dict_ops.dict_ops import (scan_exact_decoded,
                                             scan_filter_agg_exact_kernel,
                                             scan_filter_agg_sharded_kernel,
                                             scan_values_agg_exact_kernel)
from repro.kernels.dict_ops.lowered import (scan_exact_partials,
                                            scan_exact_sharded_partials,
                                            scan_values_partials)
from repro.kernels.dict_ops.ops import (_padded_corr, assemble_exact,
                                        assemble_psum_lanes, count_decodes,
                                        exact_totals, live_length,
                                        pad_bounds_pow2, pad_dictionary_pow2)
from repro.kernels.hash_probe.hash_probe import (EMPTY, probe_table,
                                                 probe_table_sharded)
from repro.kernels.hash_probe.lowered import (probe_lowered,
                                              probe_sharded_lowered)
from repro.kernels.hash_probe.ref import probe_ref


@dataclasses.dataclass
class HashTable:
    keys: np.ndarray     # (n_buckets, slots) int32, EMPTY = free
    values: np.ndarray   # (n_buckets, slots) int32

    @property
    def n_buckets(self) -> int:
        return self.keys.shape[0]


def _keys_unique(keys: np.ndarray) -> bool:
    """Uniqueness check with a fast path for sorted input: most tables are
    built over merged dictionaries, which are strictly ascending by
    construction — an O(n) diff check beats np.unique's full sort."""
    if keys.size <= 1:
        return True
    if bool(np.all(np.diff(keys) > 0)):
        return True
    return len(np.unique(keys)) == len(keys)


def build_table(keys: np.ndarray, values: np.ndarray,
                load_factor: float = 0.5, min_slots: int = 4) -> HashTable:
    """Build the fixed-slot bucket table (paper: sized to the partition so
    chains stay short; here: slots grown until the worst bucket fits)."""
    keys = np.asarray(keys, dtype=np.int32)
    values = np.asarray(values, dtype=np.int32)
    assert _keys_unique(keys), "hash table keys must be unique"
    n = max(len(keys), 1)
    n_buckets = max(8, int(2 ** np.ceil(np.log2(n / load_factor))))
    bucket = keys.astype(np.int64) % n_buckets
    counts = np.bincount(bucket, minlength=n_buckets)
    slots = max(min_slots, int(counts.max()) if len(keys) else min_slots)
    # lanes of 128 help nothing here; keep slots small & padded to 4
    slots = int(np.ceil(slots / 4) * 4)
    tk = np.full((n_buckets, slots), int(EMPTY), dtype=np.int32)
    tv = np.zeros((n_buckets, slots), dtype=np.int32)
    # vectorized slot assignment: rank within bucket = position - bucket
    # start (exclusive prefix of the bucket histogram). Narrow bucket ids
    # take numpy's radix path through stable argsort — ~9x faster than the
    # int64 comparison sort for the table sizes dictionaries produce.
    narrow = bucket.astype(np.uint16) if n_buckets <= (1 << 16) else bucket
    order = np.argsort(narrow, kind="stable")
    sorted_bucket = bucket[order]
    starts = np.cumsum(counts) - counts
    rank = np.arange(len(keys), dtype=np.int64) - starts[sorted_bucket]
    tk[sorted_bucket, rank] = keys[order]
    tv[sorted_bucket, rank] = values[order]
    # table stays host numpy: builds happen once per dictionary merge while
    # probes dispatch through jit (which converts np args cheaply), so two
    # eager device_puts per build would cost more than they save
    return HashTable(tk, tv)


def probe(table: HashTable, queries, default: int = -1,
          use_pallas: bool = True, block: int = 1024) -> np.ndarray:
    """Lookup values for queries (unique-key associative read).

    Queries may be host numpy or device arrays; the result is host numpy.
    Padding runs host-side (np is free; each eager device op costs ~35us
    on CPU) and the padded width is pow2-bucketed to bound traced shapes.
    """
    if not use_pallas:
        # reconstruct flat key/value view for the oracle
        mask = np.asarray(table.keys).reshape(-1) != int(EMPTY)
        flat_k = jnp.asarray(np.asarray(table.keys).reshape(-1)[mask])
        flat_v = jnp.asarray(np.asarray(table.values).reshape(-1)[mask])
        return np.asarray(probe_ref(jnp.asarray(queries), flat_k, flat_v,
                                    jnp.int32(default)))
    q = np.asarray(queries, dtype=np.int32)
    (n,) = q.shape
    wpad = next_pow2(max(n, 1))
    blk = min(block, wpad)
    if wpad != n:
        q = np.pad(q, (0, wpad - n))
    d = np.asarray([default], dtype=np.int32)
    mode = kernel_mode()
    if mode == "lowered":
        out = probe_lowered(q, table.keys, table.values, d)
    else:
        out = probe_table(q, table.keys, table.values, d, block=blk,
                          interpret=(mode == "interpret"))
    return np.asarray(out)[:n]


def probe_sharded(table: HashTable, query_batches, default: int = -1,
                  use_pallas: bool = True, block: int = 1024):
    """Probe every island's query batch in ONE launch (leading shard axis).

    query_batches: list of per-island int32 query arrays (ragged lengths
    allowed — they are stack-padded; padded lookups are discarded). Returns
    the per-island value arrays, elementwise identical to calling `probe`
    once per island.
    """
    lens = [int(len(q)) for q in query_batches]
    width = max(lens, default=0)
    if width == 0:
        return [np.empty(0, dtype=np.int32) for _ in query_batches]
    if not use_pallas:
        return [probe(table, q, default=default, use_pallas=False)
                for q in query_batches]
    # pow2-bucket the padded width to bound compiled shapes; pad with 0
    # (whatever a 0-key probe returns lands in a discarded slot). wpad and
    # blk are both powers of two with wpad >= blk, so wpad % blk == 0.
    # The stack stays host numpy until the single jitted dispatch.
    wpad = next_pow2(width)
    blk = min(block, wpad)
    stacked = np.zeros((len(query_batches), wpad), dtype=np.int32)
    for s, q in enumerate(query_batches):
        stacked[s, :lens[s]] = np.asarray(q, dtype=np.int32)
    d = np.asarray([default], dtype=np.int32)
    mode = kernel_mode()
    if mode == "lowered":
        out = probe_sharded_lowered(stacked, table.keys, table.values, d)
    else:
        out = probe_table_sharded(stacked, table.keys, table.values, d,
                                  block=blk,
                                  interpret=(mode == "interpret"))
    out = np.asarray(out)
    return [out[s, :lens[s]] for s in range(len(query_batches))]


# ---------------------------------------------------------------------------
# Fused join-group scan (aggregate + self-join counts, one traced call)
# ---------------------------------------------------------------------------

def _pad_join_rows(fcodes, acodes, jcodes, fvalid, jvalid, block):
    """In-trace row padding for the flat join scan (shapes key on the RAW
    row count, so callers skip every eager pad dispatch)."""
    n = fcodes.shape[0]
    pad = (-n) % block
    fv = fvalid.astype(jnp.int32)
    jv = jvalid.astype(jnp.int32)
    if pad:
        fcodes = jnp.pad(fcodes, (0, pad),
                         constant_values=jnp.iinfo(jnp.int32).max)
        acodes = jnp.pad(acodes, (0, pad))
        jcodes = jnp.pad(jcodes, (0, pad))
        fv = jnp.pad(fv, (0, pad))
        jv = jnp.pad(jv, (0, pad))
    return fcodes, acodes, jcodes, fv, jv


def _pad_join_width(fcodes, acodes, jcodes, fvalid, jvalid, block):
    """In-trace width padding for the stacked-shard join scan."""
    width = fcodes.shape[1]
    pad = (-width) % block
    fv = fvalid.astype(jnp.int32)
    jv = jvalid.astype(jnp.int32)
    if pad:
        wpad = ((0, 0), (0, pad))
        fcodes = jnp.pad(fcodes, wpad)
        acodes = jnp.pad(acodes, wpad)
        jcodes = jnp.pad(jcodes, wpad)
        fv = jnp.pad(fv, wpad)
        jv = jnp.pad(jv, wpad)
    return fcodes, acodes, jcodes, fv, jv


@functools.partial(instrumented_jit, static_argnames=("block",))
def _join_scan_lowered(fcodes, acodes, jcodes, fvalid, jvalid, adict,
                       rcount, bounds, block: int = 4096):
    fcodes, acodes, jcodes, fv, jv = _pad_join_rows(
        fcodes, acodes, jcodes, fvalid, jvalid, block)
    agg = scan_exact_partials(fcodes, acodes, fv, adict, bounds, block)
    join = scan_exact_partials(fcodes, jcodes, fv * jv, rcount,
                               bounds, block)
    return agg, join


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def _join_scan_pallas(fcodes, acodes, jcodes, fvalid, jvalid, adict,
                      rcount, bounds, alive, rlive, block: int = 4096,
                      interpret: bool = True):
    """The aggregate and the join scan's outputs, each by the decode its
    own padded width picks (`scan_filter_agg_exact_kernel`); ``alive`` and
    ``rlive`` are the live lengths of ``adict`` and ``rcount``."""
    fcodes, acodes, jcodes, fv, jv = _pad_join_rows(
        fcodes, acodes, jcodes, fvalid, jvalid, block)
    agg = scan_filter_agg_exact_kernel(fcodes, acodes, fv, adict, bounds,
                                       alive, block=block,
                                       interpret=interpret)
    join = scan_filter_agg_exact_kernel(fcodes, jcodes, fv * jv,
                                        rcount, bounds, rlive, block=block,
                                        interpret=interpret)
    return agg, join


@functools.partial(instrumented_jit, static_argnames=("block",))
def _join_scan_sharded_lowered(fcodes, acodes, jcodes, fvalid, jvalid,
                               adict, rcount, bounds, block: int = 4096):
    fcodes, acodes, jcodes, fv, jv = _pad_join_width(
        fcodes, acodes, jcodes, fvalid, jvalid, block)
    agg = scan_exact_sharded_partials(fcodes, acodes, fv, adict, bounds,
                                      block)
    join = scan_exact_sharded_partials(fcodes, jcodes, fv * jv,
                                       rcount, bounds, block)
    return agg + join


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def _join_scan_sharded_pallas(fcodes, acodes, jcodes, fvalid, jvalid,
                              adict, rcount, bounds, block: int = 4096,
                              interpret: bool = True):
    fcodes, acodes, jcodes, fv, jv = _pad_join_width(
        fcodes, acodes, jcodes, fvalid, jvalid, block)
    agg = scan_filter_agg_sharded_kernel(fcodes, acodes, fv, adict,
                                         bounds, block=block,
                                         interpret=interpret)
    join = scan_filter_agg_sharded_kernel(fcodes, jcodes, fv * jv,
                                          rcount, bounds, block=block,
                                          interpret=interpret)
    return agg + join


def scan_filter_agg_join(fcodes, acodes, jcodes, fvalid, jvalid, adict,
                         rcount, bounds, block: int = 4096):
    """One join-query group in ONE traced call (flat columns).

    For every (code_lo, code_hi) in `bounds` returns the exact
    ``(sum, count, join_count)`` triple, where sum/count aggregate
    ``adict[acodes]`` over the filter mask and join_count is the self-join
    cardinality against the build-side histogram `rcount` (int32, one
    occurrence count per join-dictionary value, valid rows only).
    """
    (n,) = fcodes.shape
    nq = len(bounds)
    if n == 0 or nq == 0:
        return [(0, 0, 0) for _ in range(nq)]
    mode = kernel_mode()
    apad, rpad = pad_dictionary_pow2(adict), pad_dictionary_pow2(rcount)
    count_decodes(apad, rpad, hist=(mode != "lowered"))
    args = (fcodes, acodes, jcodes, fvalid, jvalid, apad, rpad,
            pad_bounds_pow2(bounds))
    if mode == "lowered":
        agg, join = _join_scan_lowered(*args, block=block)
    else:
        agg, join = _join_scan_pallas(*args, live_length(adict),
                                      live_length(rcount), block=block,
                                      interpret=(mode == "interpret"))
    sums, counts = exact_totals(agg, adict)
    jsums, _ = exact_totals(join, rcount)
    return [(int(sums[q]), int(counts[q]), int(jsums[q]))
            for q in range(nq)]


def scan_filter_agg_join_sharded(fcodes, acodes, jcodes, fvalid, jvalid,
                                 adict, rcount, bounds, block: int = 4096):
    """Every island's join-query group in ONE traced call (stacked shards).

    Arrays are (n_shards, width) resident shards (padded slots carry
    valid=0); `rcount` is the GLOBAL build-side histogram (summed across
    islands — e.g. ``ShardedView.dict_counts``), so each island's partial
    join count probes the full replicated build side and the cross-island
    reduction is a plain exact sum. Returns
    ``[[(sum, count, join_count)] * Q] * n_shards``.
    """
    n_shards, width = fcodes.shape
    nq = len(bounds)
    if width == 0 or nq == 0:
        return [[(0, 0, 0)] * nq for _ in range(n_shards)]
    block = min(block, next_pow2(width))
    mode = kernel_mode()
    args = (fcodes, acodes, jcodes, fvalid, jvalid,
            pad_dictionary_pow2(adict), pad_dictionary_pow2(rcount),
            pad_bounds_pow2(bounds))
    count_decodes(*args[5:7])
    if mode == "lowered":
        parts = _join_scan_sharded_lowered(*args, block=block)
    else:
        parts = _join_scan_sharded_pallas(*args, block=block,
                                          interpret=(mode == "interpret"))
    sums, counts = assemble_exact(*parts[:4], axis=1)
    jsums, _ = assemble_exact(*parts[4:], axis=1)
    return [[(int(sums[s, q]), int(counts[s, q]), int(jsums[s, q]))
             for q in range(nq)] for s in range(n_shards)]


# ---------------------------------------------------------------------------
# Fused join-group scan WITH delta corrections (PR 9): the whole join query
# group — aggregate scan, self-join scan, and BOTH overlay corrections
# (aggregate rows and join-histogram weights) — as one traced program.
# ---------------------------------------------------------------------------

def _join_group_body(fcodes, acodes, jcodes, fvalid, jvalid, adict, rcount,
                     bounds, corr_a, corr_j, vbounds, block, cblock_a,
                     cblock_j):
    fcodes, acodes, jcodes, fv, jv = _pad_join_rows(
        fcodes, acodes, jcodes, fvalid, jvalid, block)
    agg = scan_exact_partials(fcodes, acodes, fv, adict, bounds, block)
    join = scan_exact_partials(fcodes, jcodes, fv * jv, rcount,
                               bounds, block)
    ae = scan_values_partials(corr_a[0], corr_a[1], corr_a[2], vbounds,
                              cblock_a)
    ab = scan_values_partials(corr_a[3], corr_a[4], corr_a[5], vbounds,
                              cblock_a)
    je = scan_values_partials(corr_j[0], corr_j[1], corr_j[2], vbounds,
                              cblock_j)
    jb = scan_values_partials(corr_j[3], corr_j[4], corr_j[5], vbounds,
                              cblock_j)
    return agg + join + ae + ab + je + jb


def _join_group_pallas_body(fcodes, acodes, jcodes, fvalid, jvalid, adict,
                            rcount, bounds, corr_a, corr_j, vbounds, block,
                            cblock_a, cblock_j, interpret):
    fcodes, acodes, jcodes, fv, jv = _pad_join_rows(
        fcodes, acodes, jcodes, fvalid, jvalid, block)
    agg = scan_exact_decoded(fcodes, acodes, fv, adict, bounds, block,
                             interpret)
    join = scan_exact_decoded(fcodes, jcodes, fv * jv, rcount, bounds,
                              block, interpret)
    ae = scan_values_agg_exact_kernel(corr_a[0], corr_a[1], corr_a[2],
                                      vbounds, block=cblock_a,
                                      interpret=interpret)
    ab = scan_values_agg_exact_kernel(corr_a[3], corr_a[4], corr_a[5],
                                      vbounds, block=cblock_a,
                                      interpret=interpret)
    je = scan_values_agg_exact_kernel(corr_j[0], corr_j[1], corr_j[2],
                                      vbounds, block=cblock_j,
                                      interpret=interpret)
    jb = scan_values_agg_exact_kernel(corr_j[3], corr_j[4], corr_j[5],
                                      vbounds, block=cblock_j,
                                      interpret=interpret)
    return agg + join + ae + ab + je + jb


_JG_STATICS = ("block", "cblock_a", "cblock_j")
_join_group_lowered = functools.partial(
    instrumented_jit, static_argnames=_JG_STATICS,
    name="join_group_lowered")(_join_group_body)
_join_group_pallas = functools.partial(
    instrumented_jit, static_argnames=_JG_STATICS + ("interpret",),
    name="join_group_kernel")(_join_group_pallas_body)


def scan_filter_agg_join_group(fcodes, acodes, jcodes, fvalid, jvalid,
                               adict, rcount, code_bounds, corr_a, corr_j,
                               vbounds, block: int = 4096):
    """One join-query group — base aggregate + self-join scans PLUS both
    delta corrections — in ONE traced launch.

    `corr_a` is the (6, nr) aggregate correction stack (as
    `dict_ops.scan_filter_agg_group`); `corr_j` carries [fv_eff, w_eff,
    valid_eff, fv_base, w_base, valid_base] where the w lanes are the
    effective join-histogram weights of each overlay row (int32 row counts,
    so the same split accumulator is exact). Either may be None. `rcount`
    must already be the EFFECTIVE (delta-corrected) histogram. Returns
    [(sum, count, join_count)] with the corrections folded — bit-identical
    to the compositional base scan + four scan_values_agg passes.
    """
    (n,) = fcodes.shape
    nq = len(code_bounds)
    if nq == 0:
        return []
    if n == 0:
        return [(0, 0, 0)] * nq
    ca, cblock_a = _padded_corr(corr_a)
    cj, cblock_j = _padded_corr(corr_j)
    args = (fcodes, acodes, jcodes, fvalid, jvalid,
            pad_dictionary_pow2(adict), pad_dictionary_pow2(rcount),
            pad_bounds_pow2(code_bounds), ca, cj, pad_bounds_pow2(vbounds))
    count_decodes(*args[5:7])
    mode = kernel_mode()
    if mode == "lowered":
        parts = _join_group_lowered(*args, block=block, cblock_a=cblock_a,
                                    cblock_j=cblock_j)
    else:
        parts = _join_group_pallas(*args, block=block, cblock_a=cblock_a,
                                   cblock_j=cblock_j,
                                   interpret=(mode == "interpret"))
    sums, counts = assemble_exact(*parts[0:4], axis=0)
    jsums, _ = assemble_exact(*parts[4:8], axis=0)
    aes, aec = assemble_exact(*parts[8:12], axis=0)
    abs_, abc = assemble_exact(*parts[12:16], axis=0)
    jes, _ = assemble_exact(*parts[16:20], axis=0)
    jbs, _ = assemble_exact(*parts[20:24], axis=0)
    return [(int(sums[q] + aes[q] - abs_[q]),
             int(counts[q] + aec[q] - abc[q]),
             int(jsums[q] + jes[q] - jbs[q])) for q in range(nq)]


@functools.lru_cache(maxsize=None)
def _mesh_join_call(mesh, block: int, mode: str):
    """Jitted shard_map join-group scan for one (mesh, block, mode): each
    island device runs its own aggregate + join scans over its resident
    (1, width) shard, and all eight split-accumulator components come back
    psum'd over ``ISLAND_AXIS`` as 16-bit lane pairs (exact — see
    `common.psum_split16`)."""
    def scan_exact_join_mesh(fcodes, acodes, jcodes, fvalid, jvalid, adict,
                             rcount, bounds):
        fc, ac, jc, fv, jv = _pad_join_width(
            fcodes, acodes, jcodes, fvalid, jvalid, block)
        if mode == "lowered":
            agg = scan_exact_sharded_partials(fc, ac, fv, adict, bounds,
                                              block)
            join = scan_exact_sharded_partials(fc, jc, fv * jv, rcount,
                                               bounds, block)
        else:
            agg = scan_filter_agg_sharded_kernel(
                fc, ac, fv, adict, bounds, block=block,
                interpret=(mode == "interpret"))
            join = scan_filter_agg_sharded_kernel(
                fc, jc, fv * jv, rcount, bounds, block=block,
                interpret=(mode == "interpret"))
        out = []
        for p in agg + join:     # local (1, nb, Q) -> psum'd (nb, Q) lanes
            out.extend(psum_split16(p[0], ISLAND_AXIS))
        return tuple(out)

    smapped = jax.shard_map(
        scan_exact_join_mesh, mesh=mesh,
        in_specs=(island_spec(),) * 5 + (replicated_spec(),) * 3,
        out_specs=(P(None, None),) * 16,
        check_vma=False)  # pallas_call has no replication rule
    return instrumented_jit(smapped, name="scan_exact_join_mesh")


def scan_filter_agg_join_mesh(fcodes, acodes, jcodes, fvalid, jvalid,
                              adict, rcount, bounds, mesh,
                              block: int = 4096):
    """Every island's join-query group in ONE launch on its OWN device.

    Mesh-placement sibling of `scan_filter_agg_join_sharded`: same stacked
    resident shards laid one island per device of `mesh`, same GLOBAL
    build-side histogram `rcount` (replicated to every island, like the
    dictionary), but the cross-island reduction happens ON the mesh as an
    integer psum. Returns the already-reduced
    ``[(sum, count, join_count)] * Q`` exact python ints.
    """
    n_shards, width = fcodes.shape
    nq = len(bounds)
    if width == 0 or nq == 0:
        return [(0, 0, 0)] * nq
    block = min(block, next_pow2(width))
    apad, rpad = pad_dictionary_pow2(adict), pad_dictionary_pow2(rcount)
    count_decodes(apad, rpad)
    lanes = _mesh_join_call(mesh, block, kernel_mode())(
        fcodes, acodes, jcodes, fvalid, jvalid, apad, rpad,
        pad_bounds_pow2(bounds))
    sums, counts = assemble_psum_lanes(lanes[:8])
    jsums, _ = assemble_psum_lanes(lanes[8:])
    return [(int(sums[q]), int(counts[q]), int(jsums[q]))
            for q in range(nq)]
