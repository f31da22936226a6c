"""Merge-unit kernel — the paper's update-shipping comparator tree (§5.1).

The hardware merge unit streams 8 commit-ordered FIFO queues through a
3-level comparator tree. A literal port would be a data-dependent serial
loop — hostile to the VPU. The TPU-native equivalent exploits a classic
identity: if A is ascending and B is ascending, then concat(A, reverse(B))
is *bitonic*, and a bitonic MERGE network (log2(n) stages, not the full
log^2 sort) sorts it. So an 8-way merge becomes 3 rounds of pairwise
bitonic merges — the same comparator-tree depth as the hardware unit, with
every stage vector-wide in VMEM (a reshape + select in the XLA lowering, a
lane rotation + select in the kernel; the reversal and concatenation run in
the surrounding XLA program, as Mosaic has no lane reversal).

Keys are 64-bit commit ids carried as two int32 lanes — `hi` holds the
arithmetic high word and `lo` the bias-corrected low word (see ops._split64)
— so the comparator network orders full int64 keys lexicographically on
(hi, lo) without requiring jax_enable_x64. Payloads move with their key:
a third int32 lane carries the original index, and ops.py gathers payloads
through it afterwards.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitonic_sort.bitonic_sort import lane_partner
from repro.kernels.common import instrumented_jit


def _merge_stage(hi, lo, idx, j):
    """Compare-exchange with stride 2^j, ascending (merge network stage).

    Ordering is lexicographic on (hi, lo): exactly int64 key order when the
    lanes come from ops._split64.
    """
    rows, width = hi.shape
    stride = 1 << j

    def halves(x):
        xr = x.reshape(rows, width // (2 * stride), 2, stride)
        return xr[:, :, 0, :], xr[:, :, 1, :]

    ah, bh = halves(hi)
    al, bl = halves(lo)
    ai, bi = halves(idx)
    swap = (ah > bh) | ((ah == bh) & (al > bl))

    def exchange(a, b):
        keep = jnp.where(swap, b, a)
        move = jnp.where(swap, a, b)
        return jnp.stack([keep, move], axis=2).reshape(rows, width)

    return exchange(ah, bh), exchange(al, bl), exchange(ai, bi)


def _bitonic_rows(ah, al, ai, bh, bl, bi):
    """concat(A, reverse(B)) per lane: bitonic rows of width 2*width."""
    return tuple(jnp.concatenate([a, b[:, ::-1]], axis=-1)
                 for a, b in ((ah, bh), (al, bl), (ai, bi)))


def _merge_body(ah, al, ai, bh, bl, bi):
    """Traceable merge network: concat(A, reverse(B)) is bitonic, then
    log2(width) compare-exchange stages sort it. Row-independent, so the
    whole-array lowering and the row-tiled kernel agree bit-for-bit."""
    hi, lo, idx = _bitonic_rows(ah, al, ai, bh, bl, bi)
    width = hi.shape[-1]
    for j in range(int(math.log2(width)) - 1, -1, -1):
        hi, lo, idx = _merge_stage(hi, lo, idx, j)
    return hi, lo, idx


def _merge_stage_lanes(hi, lo, idx, j):
    """`_merge_stage` by lane rotation: lane i meets lane i ^ 2^j; the
    lower lane keeps the (hi, lo)-smaller triple, the upper the larger
    (ties keep their own, as the reshape form's strict swap does)."""
    stride = 1 << j
    lane = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 1)
    ph, pl_, pi = (lane_partner(x, lane, stride) for x in (hi, lo, idx))
    gt = (hi > ph) | ((hi == ph) & (lo > pl_))
    lt = (hi < ph) | ((hi == ph) & (lo < pl_))
    lower = (lane & stride) == 0
    take = (lower & gt) | (~lower & lt)
    return (jnp.where(take, ph, hi), jnp.where(take, pl_, lo),
            jnp.where(take, pi, idx))


def _merge_kernel(h_ref, l_ref, i_ref, oh_ref, ol_ref, oi_ref):
    """Sort bitonic (rows, width) key lanes through the merge network."""
    hi, lo, idx = h_ref[...], l_ref[...], i_ref[...]
    for j in range(int(math.log2(hi.shape[-1])) - 1, -1, -1):
        hi, lo, idx = _merge_stage_lanes(hi, lo, idx, j)
    oh_ref[...] = hi
    ol_ref[...] = lo
    oi_ref[...] = idx


def _merge_pallas(ah, al, ai, bh, bl, bi, block_rows: int = 8,
                  interpret: bool = True):
    """Row-wise merge of two ascending 64-bit-keyed runs.

    Each run is (rows, width) split into int32 (hi, lo) key lanes plus an
    int32 index lane; widths are equal powers of two. Returns the merged
    (hi, lo, idx) lanes of shape (rows, 2*width).
    """
    rows, width = ah.shape
    assert bh.shape == ah.shape and rows % block_rows == 0
    spec = pl.BlockSpec((block_rows, 2 * width), lambda i: (i, 0))
    out = jax.ShapeDtypeStruct((rows, 2 * width), jnp.int32)
    return pl.pallas_call(
        _merge_kernel,
        grid=(rows // block_rows,),
        in_specs=[spec] * 3,
        out_specs=(spec, spec, spec),
        out_shape=(out, out, out),
        interpret=interpret,
    )(*_bitonic_rows(ah, al, ai, bh, bl, bi))


bitonic_merge_pair = instrumented_jit(
    _merge_pallas, static_argnames=("block_rows", "interpret"),
    name="bitonic_merge_pair")

def _merge_lanes_body(lanes):
    """Single-argument lowering: lanes is the (6, rows, width) stack
    (ah, al, ai, bh, bl, bi); returns the (3, rows, 2*width) stack
    (hi, lo, idx). One host->device conversion in, one array out — the
    cheapest possible warm dispatch on CPU."""
    hi, lo, idx = _merge_body(lanes[0], lanes[1], lanes[2],
                              lanes[3], lanes[4], lanes[5])
    return jnp.stack([hi, lo, idx])


merge_lanes_lowered = instrumented_jit(
    _merge_lanes_body, name="merge_lanes_lowered")
