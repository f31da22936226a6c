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

Decode. The dictionary decode (``dict[acodes]``) runs in the surrounding
XLA program, not in the kernel: Mosaic has no general in-kernel gather,
while XLA turns a small-dictionary take into a compare/select chain and a
large one into its native gather. The filter itself needs no decode — the
order-preserving dictionary turns value ranges into code ranges.

Exactness. Integer sums are accumulated as split 16-bit halves of the
two's-complement values, per block (each int32 partial is at most
block * 0xFFFF < 2^31 for block <= 32768); the host reassembles the exact
int64 total (`ops.assemble_exact`). That is what lets the Pallas backend
return bit-identical answers to the numpy engine's int64 histogram-dot.
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


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def scan_filter_agg_exact_kernel(fcodes, acodes, valid, dictionary, bounds,
                                 block: int = 4096, interpret: bool = True):
    """Per-block split-sum partials, each (n_blocks, Q), for Q EXCLUSIVE
    code ranges over one flat column; combined on the host."""
    parts = _scan_partials(bounds, fcodes[None], _decode(dictionary,
                                                         acodes)[None],
                           valid[None], block, False, interpret)
    return tuple(p[0] for p in parts)


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
