"""Copy-unit kernel (§6) — blocked snapshot copy with dirty-chunk predicate.

The paper's copy unit uses multiple fetch/writeback engines and a
hash-indexed tracking buffer to stream an arbitrarily-sized column at full
vault bandwidth. On TPU, split-transaction tracking is the compiler's job;
the kernel contribution is (a) VMEM-tiled streaming so the copy runs at
HBM bandwidth, and (b) a *dirty-chunk* predicate (extending the paper's
column-granularity lazy snapshotting one level finer): clean chunks are
carried over from the previous snapshot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import instrumented_jit


def _copy_kernel(dirty_ref, src_ref, prev_ref, out_ref):
    out_ref[...] = jnp.where(dirty_ref[...] != 0, src_ref[...], prev_ref[...])


@functools.partial(instrumented_jit, static_argnames=("block",))
def snapshot_copy_lowered(src, prev, dirty, block: int = 8192):
    """Jitted chunk-predicated select (CPU fast path): same per-chunk
    where() as the kernel, one whole-array op. Takes RAW (unpadded)
    columns and pads/trims in-trace, so a warm call is a single dispatch
    with no eager device glue (the traced shape keys on the raw row
    count, which is fixed for a session's table)."""
    (n,) = src.shape
    n_chunks = dirty.shape[0]
    pad = n_chunks * block - n
    if pad:
        src = jnp.pad(src, (0, pad))
        prev = jnp.pad(prev, (0, pad))
    out = jnp.where(dirty[:, None] != 0, src.reshape(n_chunks, block),
                    prev.reshape(n_chunks, block))
    return out.reshape(-1)[:n]


@functools.partial(instrumented_jit, static_argnames=("block",))
def dirty_chunks(src, prev, block: int = 8192):
    """The tracking buffer of two device-resident columns: an int32 flag
    per ``block``-row chunk, 1 where the chunk differs. Computed where the
    columns live, so neither comes back to the host."""
    (n,) = src.shape
    n_chunks = -(-n // block)
    diff = src != prev
    pad = n_chunks * block - n
    if pad:
        diff = jnp.pad(diff, (0, pad))
    return diff.reshape(n_chunks, block).any(axis=1).astype(jnp.int32)


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def snapshot_copy_kernel(src, prev, dirty, block: int = 8192,
                         interpret: bool = True):
    """Chunk-predicated copy of block-padded (n,) columns.

    Each chunk is one row of an (n_chunks, block) view, and its dirty flag
    one row of an (n_chunks, 1) column, so a grid step selects 8 whole
    chunks at once; chunk rows are padded to a multiple of 8.
    """
    (n,) = src.shape
    assert n % block == 0
    n_chunks = n // block
    assert dirty.shape == (n_chunks,)
    pad = (-n_chunks) % 8
    rows = [jnp.pad(x.reshape(n_chunks, -1), ((0, pad), (0, 0)))
            for x in (dirty[:, None], src, prev)]
    chunks = pl.BlockSpec((8, block), lambda i: (i, 0))
    out = pl.pallas_call(
        _copy_kernel,
        grid=((n_chunks + pad) // 8,),
        in_specs=[pl.BlockSpec((8, 1), lambda i: (i, 0)), chunks, chunks],
        out_specs=chunks,
        out_shape=jax.ShapeDtypeStruct((n_chunks + pad, block), src.dtype),
        interpret=interpret,
    )(*rows)
    return out[:n_chunks].reshape(n)
