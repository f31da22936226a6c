"""Public wrappers for the stage-3 re-encode of a device-resident column,
and of a column whose islands each live on their own device of a mesh.

The host side pads the batch's small arrays to pow2 widths
(``common.width_bucket``), so the traced shapes are the column length and
those widths; the dictionary's size never enters one.
"""

from __future__ import annotations

import functools

import jax
import numpy as np

from repro.distributed.sharding import island_spec, replicated_spec
from repro.kernels.common import instrumented_jit, kernel_mode, width_bucket
from repro.kernels.reencode.reencode import (reencode_rows_kernel,
                                             reencode_rows_lowered)

_I32_MAX = np.iinfo(np.int32).max


def _padded(values, width: int, fill: int) -> np.ndarray:
    out = np.full(width, fill, dtype=np.int32)
    out[:len(values)] = values
    return out


def reencode_rows(codes, valid, thresholds, write_rows, write_codes,
                  del_rows):
    """One column's stage 3 on the device: its new ``(codes, valid)``
    device arrays.

    ``codes``/``valid``: the column's device arrays; ``thresholds``: the
    sorted ``searchsorted(old_dict, new values)`` of the batch's genuinely
    new values; ``write_rows``/``write_codes``: one entry per written row,
    its last write's code in the new dictionary; ``del_rows``: the deleted
    rows. The thresholds and the writes are padded to one bucket
    (``common.width_bucket``) of the larger of them, the deletes to their
    own.
    """
    n = int(codes.shape[0])
    width = width_bucket(max(len(thresholds), len(write_rows)))
    args = (codes, valid,
            _padded(thresholds, width, _I32_MAX),
            np.array([len(thresholds)], dtype=np.int32),
            _padded(write_rows, width, n),
            _padded(write_codes, width, 0),
            _padded(del_rows, width_bucket(len(del_rows)), n))
    mode = kernel_mode()
    if mode == "lowered":
        return reencode_rows_lowered(*args)
    return reencode_rows_kernel(*args, interpret=(mode == "interpret"))


@functools.lru_cache(maxsize=None)
def _mesh_reencode_call(mesh, mode: str):
    """The jitted ``shard_map`` of one (mesh, mode): each island's device
    runs stage 3 on its own ``(1, width)`` shard with its own row ops, in
    local row ids, against the replicated thresholds."""
    def reencode_rows_mesh(codes, valid, thresholds, n_thr, w_rows, w_codes,
                           d_rows):
        args = (codes[0], valid[0], thresholds, n_thr, w_rows[0],
                w_codes[0], d_rows[0])
        if mode == "lowered":
            new, ok = reencode_rows_lowered(*args)
        else:
            new, ok = reencode_rows_kernel(*args,
                                           interpret=(mode == "interpret"))
        return new[None], ok[None]

    island, repl = island_spec(), replicated_spec()
    smapped = jax.shard_map(
        reencode_rows_mesh, mesh=mesh,
        in_specs=(island, island, repl, repl, island, island, island),
        out_specs=(island, island),
        check_vma=False)  # pallas_call has no replication rule
    return instrumented_jit(smapped, name="reencode_rows_mesh")


def reencode_rows_mesh(codes, valid, thresholds, writes, del_rows, mesh):
    """Every island's stage 3 in one launch, each on its own device.

    ``codes``/``valid``: a column's stacked ``(n_islands, width)`` shards,
    laid one island per device of ``mesh``; ``thresholds``: as for
    `reencode_rows` (the dictionary is replicated, so one vector serves
    every island); ``writes``: per island, its ``(rows, codes)`` last
    writes in local row ids; ``del_rows``: per island, its deleted local
    rows. Returns the new stacked ``(codes, valid)``, laid as the inputs.
    Padded slots hold row ``width``, which the scatters drop.
    """
    width = int(codes.shape[1])
    w = width_bucket(max([len(thresholds)] + [len(r) for r, _ in writes]))
    wd = width_bucket(max(len(d) for d in del_rows))
    return _mesh_reencode_call(mesh, kernel_mode())(
        codes, valid, _padded(thresholds, w, _I32_MAX),
        np.array([len(thresholds)], dtype=np.int32),
        np.stack([_padded(r, w, width) for r, _ in writes]),
        np.stack([_padded(c, w, 0) for _, c in writes]),
        np.stack([_padded(d, wd, width) for d in del_rows]))
