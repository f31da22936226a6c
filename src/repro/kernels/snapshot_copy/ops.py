"""Public wrappers for the copy unit, on one device and on each island's
device of a mesh."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import island_spec
from repro.kernels.common import instrumented_jit, kernel_mode
from repro.kernels.snapshot_copy.ref import snapshot_copy_ref
from repro.kernels.snapshot_copy.snapshot_copy import (snapshot_copy_kernel,
                                                       snapshot_copy_lowered)

# Below this row count the XLA:CPU dispatch alone costs more than the whole
# chunked copy, so the lowered tier does the exact select on the host.
_HOST_ROWS_MAX = 1 << 16


def snapshot_copy(src, prev, dirty, block: int = 8192,
                  use_pallas: bool = True):
    """Copy dirty chunks from src, carry clean chunks from prev.

    Accepts host numpy or device arrays; the lowered path pads and trims
    in-trace so the warm call is one jitted dispatch (no eager device ops),
    and small columns skip the dispatch entirely (host select).
    """
    (n,) = src.shape
    n_chunks = (n + block - 1) // block
    assert dirty.shape[0] == n_chunks
    if not use_pallas:
        return snapshot_copy_ref(src, prev, dirty, block)
    mode = kernel_mode()
    if mode == "lowered":
        d = np.asarray(dirty, dtype=np.int32)
        if n <= _HOST_ROWS_MAX:
            mask = np.repeat(d != 0, block)[:n]
            return np.where(mask, np.asarray(src), np.asarray(prev))
        return snapshot_copy_lowered(src, prev, d, block=block)
    pad = n_chunks * block - n
    if pad:
        src = jnp.pad(src, (0, pad))
        prev = jnp.pad(prev, (0, pad))
    out = snapshot_copy_kernel(src, prev, dirty.astype(jnp.int32),
                               block=block,
                               interpret=(mode == "interpret"))
    return out[:n]


@functools.lru_cache(maxsize=None)
def _mesh_snapshot_call(mesh, block: int, mode: str):
    """The jitted ``shard_map`` of one (mesh, block, mode): each island's
    device computes its shard's dirty chunks against the previous
    snapshot's shard and copies its own shard, as `snapshot_copy` does."""
    def snapshot_copy_mesh(src, prev):
        (n,) = src.shape[1:]
        n_chunks = -(-n // block)
        pad = n_chunks * block - n
        s, p = src[0], prev[0]
        if pad:
            s, p = jnp.pad(s, (0, pad)), jnp.pad(p, (0, pad))
        dirty = (s != p).reshape(n_chunks, block).any(axis=1)
        if mode == "lowered":
            out = jnp.where(dirty[:, None], s.reshape(n_chunks, block),
                            p.reshape(n_chunks, block)).reshape(-1)
        else:
            out = snapshot_copy_kernel(s, p, dirty.astype(jnp.int32),
                                       block=block,
                                       interpret=(mode == "interpret"))
        return out[:n][None]

    smapped = jax.shard_map(snapshot_copy_mesh, mesh=mesh,
                            in_specs=(island_spec(), island_spec()),
                            out_specs=island_spec(),
                            check_vma=False)  # pallas_call: no rule
    return instrumented_jit(smapped, name="snapshot_copy_mesh")


def snapshot_copy_mesh(src, prev, mesh, block: int = 8192):
    """Snapshot a column's stacked ``(n_islands, width)`` shards, laid one
    island per device of ``mesh``, each on its own device: chunks that
    differ from ``prev`` (the previous snapshot's shards, or ``src``
    itself where none can be carried) are copied, the others carried."""
    return _mesh_snapshot_call(mesh, block, kernel_mode())(src, prev)
