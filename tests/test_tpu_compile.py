"""Ahead-of-time TPU v5e compiles of every main-path kernel.

Each case lowers a kernel entry point with ``interpret=False`` against a
*described* (not attached) ``v5e:2x2`` topology and compiles it with the
TPU compiler installed alongside JAX, at the widths ``chip_smoke.py`` runs
on the chip. Nothing executes: this catches what the chip's compiler would
refuse (unaligned blocks, unsupported in-kernel ops, VMEM overflows) at no
chip time, and checks that each program really contains its kernels
(``tpu_custom_call``) and, on the mesh, its cross-island reduction.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, so a describe at import would
make pytest-xdist workers collect different tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from repro.distributed.sharding import (ISLAND_AXIS, island_spec,
                                        replicated_spec)
from repro.kernels.bitonic_sort.bitonic_sort import (bitonic_merge_rows,
                                                     bitonic_sort_rows)
from repro.kernels.dict_ops import ops as dict_ops
from repro.kernels.dict_ops.dict_ops import (scan_filter_agg_exact_kernel,
                                             scan_filter_agg_sharded_kernel)
from repro.kernels.hash_probe import ops as hash_ops
from repro.kernels.hash_probe.hash_probe import probe_table_sharded
from repro.kernels.merge_runs.merge_runs import bitonic_merge_pair
from repro.kernels.reencode.reencode import reencode_rows_kernel
from repro.kernels.snapshot_copy.snapshot_copy import snapshot_copy_kernel

ROWS = 1 << 24      # chip_smoke.py's table: 2^24 rows x 8 int32 columns
ISLANDS = 4         # the four-chip mesh / stacked comparison
Q = 8               # pow2-padded predicates of one query group
DICT = 4096         # a column dictionary grown by ~3k update values
SCAN_DICT = dict_ops.DICT_PAD_MIN  # a scan's padded dictionary operand
CORR = 4096         # delta-overlay correction stack width
BLOCK = 4096        # the scan entry points' default block
SHIP_ROWS = 8       # one ship batch: a row per column
LOG = 8192          # merged update-log run width


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    desc = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in specs]


I32, BOOL = jnp.int32, jnp.bool_

# name -> (jitted entry point, argument shapes, static kwargs, kernels)
CASES = {
    "scan_exact": (
        scan_filter_agg_exact_kernel,
        [((ROWS,), I32), ((ROWS,), I32), ((ROWS,), BOOL),
         ((SCAN_DICT,), I32), ((Q, 2), I32)],
        dict(block=BLOCK), 1),
    "scan_sharded": (
        scan_filter_agg_sharded_kernel,
        [((ISLANDS, ROWS // ISLANDS), I32)] * 2
        + [((ISLANDS, ROWS // ISLANDS), BOOL), ((SCAN_DICT,), I32),
           ((Q, 2), I32)],
        dict(block=BLOCK), 1),
    "scan_group": (
        dict_ops._scan_group_kernel,
        [((ROWS,), I32), ((ROWS,), I32), ((ROWS,), BOOL),
         ((SCAN_DICT,), I32), ((Q, 2), I32), ((6, CORR), I32),
         ((Q, 2), I32)],
        dict(block=BLOCK, cblock=BLOCK), 3),
    "scan_group_sharded": (
        dict_ops._scan_group_sharded_kernel,
        [((ISLANDS, ROWS // ISLANDS), I32)] * 2
        + [((ISLANDS, ROWS // ISLANDS), BOOL), ((SCAN_DICT,), I32),
           ((Q, 2), I32), ((6, CORR), I32), ((Q, 2), I32)],
        dict(block=BLOCK, cblock=BLOCK), 3),
    "scan_values_delta": (
        dict_ops._scan_values_delta_kernel,
        [((6, CORR), I32), ((Q, 2), I32)],
        dict(cblock=BLOCK), 2),
    "join_scan": (
        hash_ops._join_scan_pallas,
        [((ROWS,), I32)] * 3 + [((ROWS,), BOOL)] * 2
        + [((SCAN_DICT,), I32), ((SCAN_DICT,), I32), ((Q, 2), I32)],
        dict(block=BLOCK), 2),
    "join_group": (
        hash_ops._join_group_pallas,
        [((ROWS,), I32)] * 3 + [((ROWS,), BOOL)] * 2
        + [((SCAN_DICT,), I32), ((SCAN_DICT,), I32), ((Q, 2), I32),
           ((6, CORR), I32), ((6, CORR), I32), ((Q, 2), I32)],
        dict(block=BLOCK, cblock_a=BLOCK, cblock_j=BLOCK), 6),
    "apply_pipeline": (
        dict_ops._apply_pipeline_kernel,
        [((SHIP_ROWS, DICT), I32), ((SHIP_ROWS, 1024), I32)],
        {}, 2),
    "bitonic_sort": (
        bitonic_sort_rows, [((SHIP_ROWS, 16), I32)], dict(block_rows=8), 1),
    "bitonic_merge": (
        bitonic_merge_rows, [((SHIP_ROWS, 2 * DICT), I32)],
        dict(block_rows=8), 1),
    "probe_sharded": (
        probe_table_sharded,
        [((ISLANDS, 1024), I32), ((2 * DICT, 8), I32), ((2 * DICT, 8), I32),
         ((1,), I32)],
        dict(block=1024), 1),
    "snapshot_copy": (
        snapshot_copy_kernel,
        [((ROWS,), I32), ((ROWS,), I32), ((ROWS // 8192,), I32)],
        dict(block=8192), 1),
    "merge_pair": (
        bitonic_merge_pair, [((8, LOG), I32)] * 6, dict(block_rows=8), 1),
}
# the stage-3 re-encode at the threshold widths of one column's smallest
# and largest warm-up flush
for _w in (8, 256):
    CASES[f"reencode_w{_w}"] = (
        reencode_rows_kernel,
        [((ROWS,), I32), ((ROWS,), BOOL), ((_w,), I32), ((1,), I32),
         ((_w,), I32), ((_w,), I32), ((8,), I32)],
        {}, 1)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, specs, static, n_kernels = CASES[name]
    compiled = fn.lower(*_shapes(one_chip, *specs), interpret=False,
                        **static).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= n_kernels, name


@pytest.mark.parametrize("family", ["scan", "join"])
def test_mesh_scan_compiles_one_island_per_device(topo, family):
    """The mesh tier's shard_map program on a 4-device mesh built from the
    described chips: per-island kernels plus the on-mesh psum."""
    mesh = Mesh(topo.devices[:ISLANDS], (ISLAND_AXIS,))
    island = NamedSharding(mesh, island_spec())
    repl = NamedSharding(mesh, replicated_spec())
    width = ROWS // ISLANDS
    if family == "scan":
        call = dict_ops._mesh_scan_call(mesh, BLOCK, "compiled")
        args = (_shapes(island, ((ISLANDS, width), I32),
                        ((ISLANDS, width), I32), ((ISLANDS, width), BOOL))
                + _shapes(repl, ((SCAN_DICT,), I32), ((Q, 2), I32)))
        n_kernels = 1
    else:
        call = hash_ops._mesh_join_call(mesh, BLOCK, "compiled")
        args = (_shapes(island, *[((ISLANDS, width), I32)] * 3,
                        *[((ISLANDS, width), BOOL)] * 2)
                + _shapes(repl, ((SCAN_DICT,), I32), ((SCAN_DICT,), I32),
                          ((Q, 2), I32)))
        n_kernels = 2
    text = call.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= n_kernels
    assert "all-reduce" in text


@pytest.mark.parametrize("family", ["reencode", "snapshot"])
def test_island_program_compiles_and_stays_on_its_device(topo, family):
    """Stage 3 and the snapshot copy of islands resident on their own
    devices: one shard_map program of per-island kernels on a 4-device
    mesh of described chips, with no collective, since each island works
    on its own shard alone."""
    from repro.kernels.reencode.ops import _mesh_reencode_call
    from repro.kernels.snapshot_copy.ops import _mesh_snapshot_call

    mesh = Mesh(topo.devices[:ISLANDS], (ISLAND_AXIS,))
    island = NamedSharding(mesh, island_spec())
    repl = NamedSharding(mesh, replicated_spec())
    width = ROWS // ISLANDS
    shard = [((ISLANDS, width), I32)]
    if family == "reencode":
        call = _mesh_reencode_call(mesh, "compiled")
        w = 256   # the widest warm-up flush's thresholds and writes
        args = (_shapes(island, *shard, ((ISLANDS, width), BOOL))
                + _shapes(repl, ((w,), I32), ((1,), I32))
                + _shapes(island, ((ISLANDS, w), I32), ((ISLANDS, w), I32),
                          ((ISLANDS, 8), I32)))
    else:
        call = _mesh_snapshot_call(mesh, 8192, "compiled")
        args = _shapes(island, *shard * 2)
    text = call.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 1
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text, collective
