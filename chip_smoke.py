"""Smoke run of the Polynesia HTAP session path on a TPU.

    python chip_smoke.py              # one chip: eager and delta planes
    python chip_smoke.py --chips 4    # four chips: the island mesh only

One chip: the paper's §8 microbenchmark shape (2^24 rows x 8 int32
columns, 32 distinct values per column, write ratio 0.5, join fraction
0.5, ~50k transactions and 32 queries over 4 rounds) runs through
``HTAPSession(SystemSpec.polynesia(backend="pallas"), table)`` —
``execute``, ``query_batch``, ``advance_round``, ``finish`` — first on the
eager update plane, then with ``delta_store=True`` (overlay correction
scans and compaction). Four chips: the same eager workload on
``pallas@4/mesh`` (one analytical island per chip), compared with
``pallas@4`` stacked on one chip.

Every answer is compared with the ``numpy`` backend on the same seed. The
script fails — exit code non-zero, no result line — when JAX finds no
TPU, when the kernels would not run compiled (``REPRO_PALLAS_INTERPRET``
set to interpret them), when a compiled fused program lacks a kernel it
should hold (or, on the mesh, its cross-island all-reduce), when an island
is not on its own chip, or when any phase raises or answers differently.
Earlier lines print per-phase wall seconds and the backend compile seconds
inside them (round 0 cold, later rounds warm), the kernel mode per family,
the jit entry points each session traced, and each device's peak bytes in
use.
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# Every kernel family of the session path runs its Pallas kernel on the
# chip; none runs its XLA lowering there.
FAMILIES = ("dict_ops", "hash_probe", "bitonic_sort", "merge_runs",
            "snapshot_copy")
# The paper's §8 mix: ~50k transactions and 32 queries over 4 rounds.
TXNS, QUERIES, ROUNDS = 50_000, 32, 4
# Overlay entries per column before a compaction: below the ~3k writes each
# column takes, so the delta plane compacts during the run.
DELTA_CAPACITY = 1024


class SmokeError(RuntimeError):
    pass


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: both update planes on one chip; 4: the island "
                        "mesh vs the stacked islands only")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rows", type=int, default=1 << 24,
                   help="table rows (the paper's 2^24 by default)")
    return p.parse_args(argv)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds JAX reports spending in the backend compiler (XLA and the
    Pallas kernels it holds); tracing and lowering are not counted, as
    their events nest and would count twice."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def run_session(spec, table, txn_chunks, query_chunks, clock):
    """Drive one session round by round; returns (answers, result,
    {phase: [(wall seconds, compile seconds) per round]})."""
    from repro.core.htap import HTAPSession

    times: dict[str, list[tuple[float, float]]] = {}

    def timed(phase, fn, *args):
        c0, t0 = clock.seconds, time.perf_counter()
        out = fn(*args)
        times.setdefault(phase, []).append(
            (time.perf_counter() - t0, clock.seconds - c0))
        return out

    session = timed("open", HTAPSession, spec, table)
    answers = []
    for r, (chunk, queries) in enumerate(zip(txn_chunks, query_chunks)):
        if r:
            timed("advance_round", session.advance_round)
        timed("execute", session.execute, chunk)
        answers.extend(timed("query_batch", session.query_batch, queries))
    result = timed("finish", session.finish)
    if result.results != answers:
        raise SmokeError(f"{spec.name}: finish() results differ from the "
                         "query_batch answers")
    return answers, result, times


def report(label, times, result):
    for phase, secs in times.items():
        wall = [w for w, _ in secs]
        comp = [c for _, c in secs]
        log(f"  {label} {phase}: cold {wall[0]!r} s (compile {comp[0]!r} s), "
            f"warm {wall[1:]!r} s (compile {comp[1:]!r} s)")
    log(f"  {label} stats: compactions={result.stats.get('compactions')} "
        f"applications={result.stats.get('applications')}")
    log(f"  {label} traced entry points: "
        f"{json.dumps(result.stats.get('traces', {}), sort_keys=True)}")


def check_equal(label, got, want):
    if got != want:
        bad = [i for i, (g, w) in enumerate(zip(got, want)) if g != w]
        raise SmokeError(f"{label}: {len(bad)} of {len(want)} answers "
                         f"differ from the numpy reference (first at "
                         f"query {bad[:1]}; lengths {len(got)}/{len(want)})")
    log(f"  {label}: all {len(got)} answers equal the numpy reference")


def check_kernels_in_programs(device, rows):
    """Compile each family's fused program, as the session path calls it,
    and require its Pallas kernels (``tpu_custom_call``) in the result."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.dict_ops import ops as dict_ops
    from repro.kernels.hash_probe import ops as hash_ops
    from repro.kernels.hash_probe.hash_probe import probe_table_sharded
    from repro.kernels.merge_runs.merge_runs import bitonic_merge_pair
    from repro.kernels.snapshot_copy.snapshot_copy import \
        snapshot_copy_kernel

    sharding = jax.sharding.SingleDeviceSharding(device)

    def s(*shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    col, ok, d, b, corr = (s(rows), s(rows, dtype=jnp.bool_), s(4096),
                           s(8, 2), s(6, 4096))
    programs = {
        "dict_ops": (dict_ops._scan_group_kernel,
                     (col, col, ok, d, b, corr, b),
                     dict(block=4096, cblock=4096), 3),
        "hash_probe": (hash_ops._join_group_pallas,
                       (col, col, col, ok, ok, d, d, b, corr, corr, b),
                       dict(block=4096, cblock_a=4096, cblock_j=4096), 6),
        "hash_probe/probe": (probe_table_sharded,
                             (s(1, 1024), s(8192, 8), s(8192, 8), s(1)),
                             dict(block=1024), 1),
        "bitonic_sort": (dict_ops._apply_pipeline_kernel,
                         (s(8, 4096), s(8, 1024)), {}, 2),
        "merge_runs": (bitonic_merge_pair, (s(8, 8192),) * 6,
                       dict(block_rows=8), 1),
        "snapshot_copy": (snapshot_copy_kernel,
                          (col, col, s(rows // 8192)), dict(block=8192), 1),
    }
    for name, (fn, args, static, want) in programs.items():
        count_kernels(name, fn.lower(*args, interpret=False, **static), want)


def count_kernels(name, lowered, want):
    text = lowered.compile().as_text()
    have = text.count("tpu_custom_call")
    if have < want:
        raise SmokeError(f"compiled {name} program holds {have} "
                         f"tpu_custom_call(s), expected {want}")
    log(f"  {name}: {have} Pallas kernel(s) in the compiled program")
    return text


def check_mesh_programs(mesh, rows):
    """The mesh tier's shard_map scan and join programs must hold their
    per-island kernels and the cross-island all-reduce."""
    import jax
    import jax.numpy as jnp

    from repro.distributed.sharding import (island_sharding,
                                            replicated_sharding)
    from repro.kernels.dict_ops import ops as dict_ops
    from repro.kernels.hash_probe import ops as hash_ops

    n = mesh.devices.size
    island, repl = island_sharding(mesh), replicated_sharding(mesh)
    col = jax.ShapeDtypeStruct((n, rows // n), jnp.int32, sharding=island)
    ok = jax.ShapeDtypeStruct((n, rows // n), jnp.bool_, sharding=island)
    d, b = (jax.ShapeDtypeStruct(s, jnp.int32, sharding=repl)
            for s in ((4096,), (8, 2)))
    for name, call, args, want in (
            ("mesh scan", dict_ops._mesh_scan_call(mesh, 4096, "compiled"),
             (col, col, ok, d, b), 1),
            ("mesh join", hash_ops._mesh_join_call(mesh, 4096, "compiled"),
             (col, col, col, ok, ok, d, d, b), 2)):
        if "all-reduce" not in count_kernels(name, call.lower(*args), want):
            raise SmokeError(f"compiled {name} program has no all-reduce")


def check_islands_on_own_chips(be, column):
    """Each island's resident shard must sit on its own device."""
    view = be.shard_view(column)
    placed = {}
    for shard in view.codes.addressable_shards:
        placed[shard.index[0].start] = shard.device
    want = list(be.mesh.devices.flat)
    if sorted(placed) != list(range(be.n_shards)) or any(
            placed[s] != want[s] for s in placed):
        raise SmokeError(f"island placement {placed} is not island s on "
                         f"mesh device s ({want})")
    if len({d.id for d in placed.values()}) != be.n_shards:
        raise SmokeError(f"islands share devices: {placed}")
    log(f"  islands on their own chips: "
        f"{ {s: d.id for s, d in sorted(placed.items())} }")


def one_chip(table, txn_chunks, query_chunks, clock):
    from repro.core.htap import SystemSpec

    for plane, delta in (("eager", False), ("delta", True)):
        log(f"[{plane} plane]")
        ref, ref_res, ref_times = run_session(
            SystemSpec.polynesia(backend="numpy", n_shards=1,
                                 delta_store=delta,
                                 delta_capacity=DELTA_CAPACITY),
            table, txn_chunks, query_chunks, clock)
        report(f"numpy/{plane}", ref_times, ref_res)
        got, res, times = run_session(
            SystemSpec.polynesia(backend="pallas", n_shards=1,
                                 delta_store=delta,
                                 delta_capacity=DELTA_CAPACITY),
            table, txn_chunks, query_chunks, clock)
        report(f"pallas/{plane}", times, res)
        check_equal(f"pallas/{plane}", got, ref)
        if delta and not res.stats.get("compactions"):
            raise SmokeError("the delta plane never compacted")


def four_chips(table, txn_chunks, query_chunks, clock):
    from repro.core.backend import get_backend
    from repro.core.dsm import DSMReplica
    from repro.core.htap import SystemSpec

    mesh_be = get_backend("pallas@4/mesh")
    check_islands_on_own_chips(mesh_be, DSMReplica.from_table(
        table[:, :1]).columns[0])
    check_mesh_programs(mesh_be.mesh, table.shape[0])
    ref, ref_res, ref_times = run_session(
        SystemSpec.polynesia(backend="numpy", n_shards=1, delta_store=False),
        table, txn_chunks, query_chunks, clock)
    report("numpy", ref_times, ref_res)
    for backend in ("pallas@4/mesh", "pallas@4"):
        got, res, times = run_session(
            SystemSpec.polynesia(backend=backend, delta_store=False),
            table, txn_chunks, query_chunks, clock)
        report(backend, times, res)
        check_equal(backend, got, ref)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        raise SmokeError("the repro package (src/repro) is not next to "
                         "chip_smoke.py; run it from a checkout of the repo")
    sys.path[:0] = [os.path.join(HERE, "src"), HERE]
    import jax
    import numpy as np

    from repro.kernels import common

    backend = jax.default_backend()
    if backend != "tpu":
        raise SmokeError(f"JAX found no TPU (default backend {backend!r}); "
                         "this smoke runs on the chip only")
    mode = common.kernel_mode()
    if mode != "compiled":
        raise SmokeError(f"kernels resolve to {mode!r} mode "
                         f"(REPRO_PALLAS_INTERPRET="
                         f"{common.interpret_spec()!r}); the smoke needs "
                         "them compiled")
    devices = jax.devices()
    if len(devices) < args.chips:
        raise SmokeError(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {len(devices)}")
    log(f"compile cache: {common.use_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")
    for family in FAMILIES:
        log(f"  kernel family {family}: pallas ({mode})")

    from benchmarks.common import workload
    from repro.core.workload import split_queries, split_stream

    t0 = time.perf_counter()
    table, stream, queries = workload(
        np.random.default_rng(args.seed), n_rows=args.rows, n_cols=8,
        n_txn=TXNS, n_queries=QUERIES, write_ratio=0.5,
        join_fraction=0.5)
    txn_chunks = split_stream(stream, ROUNDS)
    query_chunks = split_queries(queries, ROUNDS)
    log(f"workload: {args.rows} rows x 8 cols, {len(stream)} txns, "
        f"{len(queries)} queries, {ROUNDS} rounds, seed {args.seed} "
        f"({time.perf_counter() - t0!r} s to generate)")

    if args.chips == 4:
        four_chips(table, txn_chunks, query_chunks, CompileClock())
    else:
        # what a stacked-tier query group pays to ship its columns to the
        # chip
        t0 = time.perf_counter()
        cols = [jax.device_put(table[:, c]) for c in range(3)]
        jax.block_until_ready(cols)
        log(f"host->device copy of 3 columns ({3 * table[:, 0].nbytes} "
            f"bytes): {time.perf_counter() - t0!r} s")
        del cols
        t0 = time.perf_counter()
        check_kernels_in_programs(devices[0], args.rows)
        log(f"kernel check compiles: {time.perf_counter() - t0!r} s")
        one_chip(table, txn_chunks, query_chunks, CompileClock())

    for dev in devices[:args.chips]:
        stats = dev.memory_stats() or {}
        log(f"peak_bytes_in_use (device {dev.id}): "
            f"{stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # any failed phase: say why, print no result
        if not isinstance(e, SmokeError):
            import traceback
            traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
