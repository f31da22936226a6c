"""Fused analytical-scan kernel (§7): filter -> aggregate, one pass.

The paper's analytical engine runs scan/filter/aggregate operator instances
on 1000-tuple segments inside each vault. The PIM win is that the segment
never leaves the vault. The TPU analog: a grid step pulls one (rows, 128)
tile of the filter column, the aggregate values and the validity into VMEM,
applies every query's range predicate to it, and accumulates exact
per-block partial sums — one sequential read of each column per query
group.

Layout. Columns ride as ``(S, n / 128, 128)`` int32 tiles (``S`` stacked
islands; a flat column is ``S = 1``), so every block is (8, 128)-aligned,
and the Q query bounds are scalar-prefetched into SMEM. Each grid step
writes its (4, Q) partials as one output block.

Decode. The filter needs no decode: the order-preserving dictionary turns
value ranges into code ranges. The aggregate column is decoded one of
three ways, chosen from its padded width (`decode_path`):

* ``select``: up to `DICT_SELECT_MAX` padded entries, ``dict[acodes]`` in
  the surrounding XLA program, which XLA turns into a compare/select chain
  (Mosaic has no general in-kernel gather).
* ``hist``: past it, no decode at all. A second kernel counts each
  query's aggregate codes over its masked rows on the MXU
  (`_hist_counts`), and the host takes one exact int64 dot of the counts
  with the dictionary: ``SUM(dict[a]) = sum_c H[c] * dict[c]``.
* ``gather``: past it, in the programs without the histogram branch (the
  stacked, mesh and delta-plane scans), ``dict[acodes]`` again, which XLA
  lowers to its native gather.

Exactness. Integer sums are accumulated as split 16-bit halves of the
two's-complement values, per block (each int32 partial is at most
block * 0xFFFF < 2^31 for block <= 32768); the host reassembles the exact
int64 total (`ops.assemble_exact`). A histogram's counts are exact too:
0/1 one-hots contract in f32 over at most `HIST_GROUP` * 128 rows, and
the grid adds them in int32 (a count is at most the row count). Either way
the Pallas backend returns bit-identical answers to the numpy engine's
int64 histogram-dot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, instrumented_jit

MIN_BLOCK = 8 * LANES    # one (8, 128) int32 tile
MAX_BLOCK = 1 << 15      # keeps every 16-bit partial of a block below 2^31

# The widest padded dictionary XLA decodes by a compare/select chain: on a
# v5e a 2^24-row decode takes about 3 ms at 32 or 64 entries, and XLA's
# native gather takes 115-166 ms at every width from 128 to 2^17 (PERF.md,
# section 6).
DICT_SELECT_MAX = 64
# A histogram splits each code as c = HIST_LO * h + l: a 0/1 one-hot of the
# low parts (HIST_LO, rows) is shared by the queries of a block, one of the
# high parts (HIST_HI_CHUNK, rows) per query and chunk of
# HIST_HI_CHUNK * HIST_LO codes.
HIST_LO_BITS = 8
HIST_LO = 1 << HIST_LO_BITS
HIST_HI_CHUNK = 128
HIST_BLOCK = 1 << 14     # rows per grid step
HIST_GROUP = 16          # (16, 128) rows per MXU contraction: 2048 rows
HIST_QUERIES = 4         # queries per output block, which bounds the VMEM


def decode_path(width: int, hist: bool = True) -> str:
    """How a scan decodes a dictionary of padded ``width``: ``select``,
    ``hist`` or ``gather`` (see the module doc). ``hist=False`` is a
    program without the histogram branch: the stacked, mesh and
    delta-plane scans and the lowered CPU path."""
    if width <= DICT_SELECT_MAX:
        return "select"
    return "hist" if hist else "gather"


def _make_scan_kernel(nq: int, inclusive: bool):
    """Kernel body for `nq` predicates; `inclusive` selects lo <= f <= hi
    (raw-value overlay scans) over the code-range lo <= f < hi."""

    def kernel(bounds_ref, f_ref, a_ref, v_ref, out_ref):
        f = f_ref[...]                          # (rows, 128) filter column
        a = a_ref[...]                          # decoded aggregate values
        ok = v_ref[...] != 0
        lo16 = a & 0xFFFF                       # low half of u32(a)
        hi16 = (a >> 16) & 0xFFFF               # high half (mask kills sign)
        neg = (a < 0).astype(jnp.int32)
        part = jax.lax.broadcasted_iota(jnp.int32, (4, nq), 0)
        query = jax.lax.broadcasted_iota(jnp.int32, (4, nq), 1)
        out = jnp.zeros((4, nq), jnp.int32)
        for q in range(nq):
            lo, hi = bounds_ref[q, 0], bounds_ref[q, 1]
            upper = (f <= hi) if inclusive else (f < hi)
            m = (f >= lo) & upper & ok
            sums = (jnp.sum(jnp.where(m, lo16, 0)),
                    jnp.sum(jnp.where(m, hi16, 0)),
                    jnp.sum(m.astype(jnp.int32)),
                    jnp.sum(jnp.where(m, neg, 0)))
            for k, s in enumerate(sums):
                out = jnp.where((part == k) & (query == q), s, out)
        out_ref[...] = out

    return kernel


def _scan_partials(bounds, f, a, valid, block: int, inclusive: bool,
                   interpret: bool):
    """Traced: (S, n) filter column, aggregate VALUES and validity ->
    (lo16, hi16, cnt, neg) per-block partials, each (S, n_blocks, Q).

    Rows are padded in-trace to a multiple of the block (valid=0 is the
    scan identity); blocks below one (8, 128) tile are raised to it.
    """
    block = max(MIN_BLOCK, block)
    assert block % MIN_BLOCK == 0 and block <= MAX_BLOCK, block
    s, n = f.shape
    v = valid.astype(jnp.int32)
    pad = (-n) % block
    if pad:
        wpad = ((0, 0), (0, pad))
        f, a, v = jnp.pad(f, wpad), jnp.pad(a, wpad), jnp.pad(v, wpad)
    n_blocks = (n + pad) // block
    tiles = [x.astype(jnp.int32).reshape(s, -1, LANES) for x in (f, a, v)]
    nq = bounds.shape[0]
    tile = pl.BlockSpec((None, block // LANES, LANES),
                        lambda i, j, b: (i, j, 0))
    out = pl.pallas_call(
        _make_scan_kernel(nq, inclusive),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(s, n_blocks),
            in_specs=[tile, tile, tile],
            out_specs=pl.BlockSpec((None, None, 4, nq),
                                   lambda i, j, b: (i, j, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((s, n_blocks, 4, nq), jnp.int32),
        interpret=interpret,
    )(bounds.astype(jnp.int32), *tiles)
    return tuple(out[:, :, k, :] for k in range(4))


def _decode(dictionary, codes):
    """Dictionary decode in the surrounding XLA program (see module doc)."""
    return jnp.take(dictionary.astype(jnp.int32), codes, mode="clip")


def scan_exact_decoded(fcodes, acodes, valid, dictionary, bounds, block,
                       interpret):
    """Traced: the decoding scan of one flat column (``select`` or
    ``gather``), per-block split-sum partials, each (n_blocks, Q)."""
    parts = _scan_partials(bounds, fcodes[None], _decode(dictionary,
                                                         acodes)[None],
                           valid[None], block, False, interpret)
    return tuple(p[0] for p in parts)


def _onehot_t(x, n: int, base):
    """(HIST_GROUP, 128) int32 -> (n, HIST_GROUP * 128) bf16 with
    ``[i, r] = x[r] == base + i``, the rows of ``x`` laid along the lanes
    128 at a time: each sublane row broadcasts down the sublanes, so
    nothing is transposed."""
    ids = jax.lax.broadcasted_iota(jnp.int32, (n, LANES), 0) + base
    return jnp.concatenate(
        [jnp.where(ids == x[i:i + 1, :], 1.0, 0.0).astype(jnp.bfloat16)
         for i in range(x.shape[0])], axis=1)


def _make_hist_kernel(nq: int, n_chunks: int):
    """Kernel body for a block of ``nq`` queries: per query, the counts of
    the codes over its masked rows, accumulated into the block's
    (nq, hi, HIST_LO) output, which the grid revisits along the rows.
    Chunks of high parts wholly at or past the live dictionary length and
    empty queries are skipped."""
    chunk = HIST_HI_CHUNK * HIST_LO
    contract = (((1,), (1,)), ((), ()))

    def kernel(bounds_ref, live_ref, f_ref, c_ref, v_ref, out_ref):
        @pl.when(pl.program_id(1) == 0)
        def _():
            out_ref[...] = jnp.zeros_like(out_ref)

        live = live_ref[0]
        first = pl.program_id(0) * nq

        def group(g, carry):
            rows = pl.ds(pl.multiple_of(g * HIST_GROUP, HIST_GROUP),
                         HIST_GROUP)
            f, c = f_ref[rows, :], c_ref[rows, :]
            ok = v_ref[rows, :] != 0
            low = _onehot_t(c & (HIST_LO - 1), HIST_LO, 0)
            high = c >> HIST_LO_BITS
            for q in range(nq):
                lo, hi = bounds_ref[first + q, 0], bounds_ref[first + q, 1]
                # a masked-out row's high part matches no chunk
                hq = jnp.where((f >= lo) & (f < hi) & ok, high, -1)

                @pl.when(lo < hi)
                def _():
                    for k in range(n_chunks):
                        @pl.when(k * chunk < live)
                        def _():
                            base = k * HIST_HI_CHUNK
                            counts = jax.lax.dot_general(
                                _onehot_t(hq, HIST_HI_CHUNK, base), low,
                                contract, preferred_element_type=jnp.float32)
                            sl = pl.ds(base, HIST_HI_CHUNK)
                            out_ref[q, sl, :] += counts.astype(jnp.int32)
            return carry

        jax.lax.fori_loop(0, HIST_BLOCK // LANES // HIST_GROUP, group, 0)

    return kernel


def _hist_counts(bounds, fcodes, codes, valid, live, width: int,
                 interpret: bool):
    """Traced: (Q, H, HIST_LO) int32 counts of ``codes`` over each query's
    rows ``lo <= f < hi`` with ``valid``; flattened, entry c counts code c
    for every c below ``width`` (rounded up to whole chunks). ``live``, a
    (1,) int32 array, is the dictionary's live length. Rows are padded
    in-trace to a multiple of `HIST_BLOCK`; the grid runs the queries in
    blocks of at most `HIST_QUERIES` (Q is a power of two)."""
    (n,) = fcodes.shape
    v = valid.astype(jnp.int32)
    pad = (-n) % HIST_BLOCK
    if pad:
        fcodes, codes, v = (jnp.pad(x, (0, pad)) for x in (fcodes, codes, v))
    chunk = HIST_HI_CHUNK * HIST_LO
    n_chunks = -(-width // chunk)
    nq = bounds.shape[0]
    qb = min(nq, HIST_QUERIES)
    tile = pl.BlockSpec((HIST_BLOCK // LANES, LANES),
                        lambda i, j, b, k: (j, 0))
    out_shape = (nq, n_chunks * HIST_HI_CHUNK, HIST_LO)
    # the output block, the one-hots and the f32 counts of one contraction
    vmem = (2 * 4 * qb * n_chunks * chunk
            + 2 * (HIST_LO + HIST_HI_CHUNK) * HIST_GROUP * LANES
            + 4 * HIST_HI_CHUNK * HIST_LO)
    return pl.pallas_call(
        _make_hist_kernel(qb, n_chunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(nq // qb, (n + pad) // HIST_BLOCK),
            in_specs=[tile, tile, tile],
            out_specs=pl.BlockSpec((qb,) + out_shape[1:],
                                   lambda i, j, b, k: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret,
    )(bounds.astype(jnp.int32), live.astype(jnp.int32),
      *(x.astype(jnp.int32).reshape(-1, LANES) for x in (fcodes, codes, v)))


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def scan_filter_agg_exact_kernel(fcodes, acodes, valid, dictionary, bounds,
                                 live, block: int = 4096,
                                 interpret: bool = True):
    """Q EXCLUSIVE code ranges over one flat column, by the decode that
    `decode_path` picks from the dictionary's padded width: a 1-tuple of
    (Q, H, HIST_LO) code counts (``hist``; ``live`` is the dictionary's
    live length, a (1,) array, which the other paths leave unused), else
    per-block split-sum partials, each (n_blocks, Q). Combined on the host
    (`ops.exact_totals`)."""
    if decode_path(dictionary.shape[0]) == "hist":
        return (_hist_counts(bounds, fcodes, acodes, valid, live,
                             dictionary.shape[0], interpret),)
    return scan_exact_decoded(fcodes, acodes, valid, dictionary, bounds,
                              block, interpret)


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def scan_filter_agg_sharded_kernel(fcodes, acodes, valid, dictionary, bounds,
                                   block: int = 4096, interpret: bool = True):
    """One launch over (n_shards, width) stacked shards x Q fused queries:
    partials of shape (n_shards, n_blocks, Q). Grid step (s, i) scans block
    i of island s's resident shard; padded slots carry valid=0."""
    return _scan_partials(bounds, fcodes, _decode(dictionary, acodes),
                          valid, block, False, interpret)


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def scan_values_agg_exact_kernel(fvals, avals, valid, bounds,
                                 block: int = 4096, interpret: bool = True):
    """Raw-value correction scan — the delta-overlay pass of a merged read.

    The filter column holds raw VALUES (overlay rows are decoded at append
    time, so the dictionary pushdown does not apply), so bounds are
    INCLUSIVE value ranges, and the aggregate values need no decode.
    Partials are (n_blocks, Q), as `scan_filter_agg_exact_kernel`'s.
    """
    parts = _scan_partials(bounds, fvals[None], avals[None], valid[None],
                           block, True, interpret)
    return tuple(p[0] for p in parts)
