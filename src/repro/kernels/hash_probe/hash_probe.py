"""Hash-lookup-unit kernel (§5.1/§5.2) — bucketed probe, TPU adaptation.

The paper's hash unit decouples hash computation from bucket traversal and
runs 4 probe units in parallel over linked-list buckets, with a reorder
buffer to preserve commit order. Pointer chasing has no efficient TPU
analogue (DESIGN.md §2), so the TPU-native layout replaces linked buckets
with *fixed-slot open buckets*: a (n_buckets, slots) keys table and a
matching values table. A probe hashes each query key (modulo hash, like the
paper) and fetches its bucket row in the surrounding XLA program — Mosaic
has no general in-kernel gather — and the kernel compares all slots of a
lane-dense (slots, block) tile of bucket rows vector-wide: the "4
concurrent probe units" become a 128-lane compare. Commit order is
preserved for free: outputs stay in query order (no reorder buffer needed
— noted as an adaptation win).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import LANES, instrumented_jit

EMPTY = jnp.int32(-2147483648)  # reserved empty-slot key


def _probe_kernel(default_ref, q_ref, bk_ref, bv_ref, out_ref):
    q = q_ref[...]                      # (1, blk) query keys
    bk = bk_ref[...]                    # (slots, blk) their bucket rows
    hit = bk == q                       # vector-wide slot compare
    val = jnp.max(jnp.where(hit, bv_ref[...], jnp.iinfo(jnp.int32).min),
                  axis=0, keepdims=True)
    found = jnp.max(hit.astype(jnp.int32), axis=0, keepdims=True) > 0
    out_ref[...] = jnp.where(found, val, default_ref[0])


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def probe_table(queries, table_keys, table_vals, default, block: int = 1024,
                interpret: bool = True):
    """Probe `queries` (n,) against the bucketed table; miss -> default.
    Queries are padded in-trace to a multiple of the (>= 128-lane) block."""
    (n,) = queries.shape
    block = max(block, LANES)
    pad = (-n) % block
    q = jnp.pad(queries, (0, pad))[None, :]
    n_buckets, slots = table_keys.shape
    bucket = jax.lax.rem(q[0], n_buckets)   # the paper's modulo hash
    bucket = jnp.where(bucket < 0, bucket + n_buckets, bucket)
    bk, bv = (jnp.take(t, bucket, axis=0).T for t in (table_keys, table_vals))
    row = pl.BlockSpec((1, block), lambda i, d: (0, i))
    rows = pl.BlockSpec((slots, block), lambda i, d: (0, i))
    out = pl.pallas_call(
        _probe_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=((n + pad) // block,),
            in_specs=[row, rows, rows], out_specs=row),
        out_shape=jax.ShapeDtypeStruct(q.shape, table_vals.dtype),
        interpret=interpret,
    )(default, q, bk, bv)
    return out[0, :n]


@functools.partial(instrumented_jit, static_argnames=("block", "interpret"))
def probe_table_sharded(queries, table_keys, table_vals, default,
                        block: int = 1024, interpret: bool = True):
    """Probe a (n_shards, width) stacked query batch in one launch — the
    probe is elementwise, so the islands' batches ride one flat probe."""
    out = probe_table(queries.reshape(-1), table_keys, table_vals, default,
                      block=block, interpret=interpret)
    return out.reshape(queries.shape)
