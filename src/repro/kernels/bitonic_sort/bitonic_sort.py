"""Bitonic sort kernel — the paper's 1024-value sort unit (§5.2).

Polynesia's update-application accelerator sorts the <=1024 pending update
values with a hardware bitonic network (0.18 mm^2, Q100-class [72]). The
TPU adaptation keeps the *data-independent comparator network* property —
which is what made it cheap in hardware — so there are no gathers and no
data-dependent control flow; the VPU executes each stage vector-wide.

A (rows, width) tile is sorted row-wise; `width` must be a power of two
(callers pad with +inf sentinels). For width=1024 the network has
log2(1024)*(log2(1024)+1)/2 = 55 compare-exchange stages, fully unrolled at
trace time. Each stage has two forms with identical results: a reshape +
min/max (`_compare_exchange`, the XLA lowering) and, inside the kernel, a
lane rotation + select (`_compare_exchange_lanes`) — Mosaic cannot
relayout the reshape's 4-D intermediate.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, instrumented_jit


def _compare_exchange(x: jnp.ndarray, k: int, j: int) -> jnp.ndarray:
    """One bitonic stage on rows of x: partner stride 2^j within 2^k blocks.

    Indices i and i^(2^j) compare; direction ascends iff bit k of i is 0.
    Because stride 2^(j+1) divides 2^k, every contiguous pair-group shares
    the same direction, so the stage is a reshape + min/max + where.
    """
    rows, width = x.shape
    stride = 1 << j
    xr = x.reshape(rows, width // (2 * stride), 2, stride)
    a = xr[:, :, 0, :]
    b = xr[:, :, 1, :]
    lo = jnp.minimum(a, b)
    hi = jnp.maximum(a, b)
    # direction per pair-group: ascending iff bit k of the base index is 0
    base = jnp.arange(width // (2 * stride), dtype=jnp.int32) * (2 * stride)
    asc = ((base >> k) & 1) == 0  # (groups,)
    first = jnp.where(asc[None, :, None], lo, hi)
    second = jnp.where(asc[None, :, None], hi, lo)
    return jnp.stack([first, second], axis=2).reshape(rows, width)


def lane_partner(x: jnp.ndarray, lane: jnp.ndarray, stride: int):
    """Value at lane ``i ^ stride`` of each row, by lane rotation.

    Both rotations are taken and the lane index rides through the same
    rotation, so the select is right whichever way the rotation turns.
    """
    width = x.shape[-1]
    fwd = pltpu.roll(x, stride, 1)
    src = pltpu.roll(lane, stride, 1)
    return jnp.where(src == (lane ^ stride), fwd,
                     pltpu.roll(x, width - stride, 1))


def _compare_exchange_lanes(x: jnp.ndarray, k: int, j: int) -> jnp.ndarray:
    """`_compare_exchange` without the reshape: lane i meets its partner
    i ^ 2^j and keeps the min when its (lower-half, ascending) flags
    agree, the max otherwise."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    p = lane_partner(x, lane, 1 << j)
    lower = (lane & (1 << j)) == 0
    asc = ((lane >> k) & 1) == 0
    return jnp.where(lower == asc, jnp.minimum(x, p), jnp.maximum(x, p))


def _bitonic_network(x: jnp.ndarray, cx=_compare_exchange) -> jnp.ndarray:
    width = x.shape[-1]
    log_n = int(math.log2(width))
    assert (1 << log_n) == width, "width must be a power of two"
    for k in range(1, log_n + 1):
        for j in range(k - 1, -1, -1):
            x = cx(x, k, j)
    return x


def _bitonic_merge_network(x: jnp.ndarray,
                           cx=_compare_exchange) -> jnp.ndarray:
    """Merge rows whose halves form a bitonic sequence into sorted rows.

    With A sorted ascending and B appended reversed, each row is bitonic,
    so only the final log2(width) half-cleaner stages of the full network
    are needed. Bit log2(width) of every in-row index is 0, so every stage
    runs all-ascending — the device half of the fused apply pipeline's
    dictionary merge.
    """
    width = x.shape[-1]
    log_n = int(math.log2(width))
    assert (1 << log_n) == width, "width must be a power of two"
    for j in range(log_n - 1, -1, -1):
        x = cx(x, log_n, j)
    return x


def _sort_kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_network(x_ref[...], _compare_exchange_lanes)


def _merge_kernel(x_ref, o_ref):
    o_ref[...] = _bitonic_merge_network(x_ref[...], _compare_exchange_lanes)


# Jitted whole-array network (CPU fast path). The network is row-
# independent, so this matches the row-tiled kernel bit-for-bit.
bitonic_sort_rows_lowered = instrumented_jit(
    _bitonic_network, name="bitonic_sort_rows_lowered")


@functools.partial(instrumented_jit, static_argnames=("block_rows", "interpret"))
def bitonic_sort_rows(x: jnp.ndarray, block_rows: int = 8,
                      interpret: bool = True) -> jnp.ndarray:
    """Row-wise bitonic sort of a (rows, width) array; width a power of 2.

    Grid tiles rows in `block_rows` chunks; each kernel invocation holds a
    (block_rows, width) tile in VMEM (width=1024 int32 -> 32 KiB/tile at
    block_rows=8, well inside the ~16 MiB VMEM budget). Rows narrower than
    one lane-width are sentinel-padded to it and trimmed afterwards.
    """
    rows, width = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    lanes = max(width, LANES)
    if lanes != width:
        top = (jnp.iinfo(x.dtype).max if jnp.issubdtype(x.dtype, jnp.integer)
               else jnp.inf)
        x = jnp.pad(x, ((0, 0), (0, lanes - width)), constant_values=top)
    out = pl.pallas_call(
        _sort_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, lanes), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, lanes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, lanes), x.dtype),
        interpret=interpret,
    )(x)
    return out[:, :width]


@functools.partial(instrumented_jit, static_argnames=("block_rows", "interpret"))
def bitonic_merge_rows(x: jnp.ndarray, block_rows: int = 8,
                       interpret: bool = True) -> jnp.ndarray:
    """Row-wise bitonic MERGE of (rows, width) bitonic rows (asc ++ desc).

    The final log2(width) half-cleaner stages only — the merge unit of the
    fused apply pipeline. Same tiling budget as `bitonic_sort_rows`; rows
    are at least one lane-width (callers widen the bitonic row's sentinel
    gap, which keeps it bitonic).
    """
    rows, width = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    assert width >= LANES, width
    return pl.pallas_call(
        _merge_kernel,
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, width), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, width), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, width), x.dtype),
        interpret=interpret,
    )(x)
