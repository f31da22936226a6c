"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus paper-claim check tables
on stderr-style stdout lines prefixed with spaces).

Usage: python -m benchmarks.run [figN|serve|ci] [--backend=numpy|pallas]
                                [--shards=N] [--timing=phase|timeline]
                                [--json=PATH]

--backend selects the execution backend (core/backend.py) for every system
driver; the REPRO_BACKEND environment variable does the same. --shards
fans analytics out over N analytical islands (ShardedBackend; REPRO_SHARDS
works too). --timing selects the cost model — whole-run phase buckets
("phase") or the round-by-round discrete-event timeline ("timeline",
core/timeline.py); REPRO_TIMING works too. The ``ci`` tag runs the small
fixed CI workload over numpy/pallas x shards {1, 4}, the mesh placement
tier (pallas@4/mesh, when 4 devices are available — REPRO_HOST_DEVICES=4
through run.sh forces them on CPU), plus one async-timeline and one
incremental (HTAPSession, mid-round chunked) configuration and writes the
throughput gate file (--json, default BENCH_ci.json) compared by
tools/check_bench.py. The ``serve`` tag is the open-system mixed-traffic
sweep (benchmarks/fig_serve.py).
"""

import json
import sys
import time

USAGE = ("usage: python -m benchmarks.run [figN|serve|ci] [--backend=NAME] "
         "[--shards=N] [--timing=phase|timeline] [--json=PATH]")

# (label, spec overrides). The timeline combo prices the very same
# Polynesia run with the discrete-event model (async propagation): its
# answers must match the phase combos bit-for-bit, and its modeled
# throughput/freshness are gated like any other row. The session-chunked
# combo drives the same rounds through HTAPSession with each round's txn
# chunk split in two — the incremental surface must stay at exact parity
# with the batch wrappers (answers AND modeled throughput).
CI_MATRIX = [
    ("numpy@1", dict(backend="numpy", n_shards=1)),
    ("numpy@4", dict(backend="numpy", n_shards=4)),
    ("pallas@1", dict(backend="pallas", n_shards=1)),
    ("pallas@4", dict(backend="pallas", n_shards=4)),
    # mesh placement tier: the same 4 islands, one per device of a jax
    # mesh (needs 4 devices — run.sh REPRO_HOST_DEVICES=4 forces them on
    # CPU; ci_bench skips the combo with a notice when they're missing)
    ("pallas@4/mesh", dict(backend="pallas@4/mesh")),
    ("numpy@1+timeline-async",
     dict(backend="numpy", n_shards=1, timing="timeline",
          async_propagation=True)),
    ("numpy@1+session-chunked",
     dict(backend="numpy", n_shards=1, session_chunked=True)),
    # delta-store update plane vs the eager Phase-2 swap, both on the sync
    # timeline so freshness is measured: answers must be bit-identical
    # (enforced against the whole matrix below) and check_bench holds the
    # delta combo's txn throughput and freshness to the eager row
    ("pallas@1+timeline", dict(backend="pallas", n_shards=1,
                               timing="timeline")),
    ("pallas@1+delta", dict(backend="pallas", n_shards=1,
                            timing="timeline", delta_store=True)),
    # elastic resharding (core/elastic.py): the same rounds driven through
    # HTAPSession with the island count resized 1 -> 4 -> 2 mid-run at
    # round boundaries; answers must stay bit-identical to the whole
    # matrix, and check_bench holds its launch count to the pallas@1 row
    # (the rebalance is a host-side repartition, not extra kernel traffic)
    ("pallas@1+resize", dict(backend="pallas", n_shards=1,
                             timing="timeline", session_resize=(4, 2))),
]


def _mesh_devices_missing(label: str) -> int | None:
    """Devices a mesh combo needs beyond what the process has (None=runnable)."""
    if "/mesh" not in label:
        return None
    import jax

    from repro.core.backend import parse_backend_spec
    need = parse_backend_spec(label.split("+")[0]).n_shards or 1
    return need if jax.device_count() < need else None


def _run_polynesia(table, stream, queries, n_rounds, **overrides):
    """One CI combo: the batch wrapper, or an HTAPSession driven
    incrementally — with sub-round txn chunks (session_chunked=True)
    and/or a mid-run island-resize schedule (session_resize=(n1, n2, ...)
    resizes to n_i after round i's query batch)."""
    from repro.core import htap
    from repro.core.workload import split_queries, split_stream

    session_chunked = overrides.pop("session_chunked", False)
    session_resize = overrides.pop("session_resize", ())
    if not session_chunked and not session_resize:
        return htap.run("Polynesia", table, stream, queries,
                        n_rounds=n_rounds, **overrides)
    session = htap.HTAPSession(htap.SystemSpec.polynesia(**overrides), table)
    for r, (txn_chunk, q_chunk) in enumerate(
            zip(split_stream(stream, n_rounds),
                split_queries(queries, n_rounds))):
        if r:
            session.advance_round()
        subs = (split_stream(txn_chunk, 2)   # mid-round chunk boundary
                if session_chunked else [txn_chunk])
        for sub in subs:
            session.execute(sub)
        session.query_batch(q_chunk)
        if r < len(session_resize):
            session.resize_islands(session_resize[r])
    return session.finish()


def ci_bench(json_path: str) -> None:
    """Small fixed workload -> modeled throughput gate file.

    Runs Polynesia over the backend x shard (x timing) matrix; every combo
    must produce the same (bit-identical) query answers, and each combo's
    modeled txn/ana throughput lands in the JSON that CI compares against
    benchmarks/baseline.json. Modeled throughputs are deterministic
    (analytic cost model over a seeded workload), so a regression gate on
    them is machine-independent.
    """
    import numpy as np

    from benchmarks.common import ci_workload

    metrics = {}
    traces = {}
    answers = None
    for label, kwargs in CI_MATRIX:
        need = _mesh_devices_missing(label)
        if need is not None:
            print(f"# skipping {label}: needs {need} devices (have fewer); "
                  f"force them with REPRO_HOST_DEVICES={need} through "
                  "benchmarks/run.sh, or XLA_FLAGS="
                  f"--xla_force_host_platform_device_count={need}")
            continue
        table, stream, queries = ci_workload()
        # cold pass: counts kernel dispatches and eats the jit compiles;
        # its wall clock is reported separately (cold_s) so compile cost
        # stays visible without polluting the steady-state column
        from repro.core.backend import counting_kernel_calls
        t0 = time.perf_counter()
        with counting_kernel_calls() as counts:
            res = _run_polynesia(table, stream, queries, 4, **dict(kwargs))
        cold_s = time.perf_counter() - t0
        # warm passes: the measured wall-clock column. Compile caches are
        # hot, so each pass is steady-state execution; wall_s is the best
        # of three (min is the standard noise-robust estimator for timing
        # under scheduler jitter), which keeps the machine-independent
        # ratio gates in tools/check_bench.py stable.
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            res2 = _run_polynesia(table, stream, queries, 4, **dict(kwargs))
            walls.append(time.perf_counter() - t0)
            if res2.results != res.results:
                sys.exit(f"CI bench: {label} warm-run answers diverged — "
                         "nondeterministic execution")
        wall_s = min(walls)
        if answers is None:
            answers = res.results
        elif answers != res.results:
            sys.exit(f"CI bench: {label} answers diverged from "
                     "the first combo — exactness contract broken")
        metrics[label] = {
            "txn_tps": res.txn_throughput,
            "ana_qps": res.ana_throughput,
            # measured wall clock: warm steady state vs first-call compile
            # cost, next to the modeled throughputs. The warm column backs
            # the pallas-vs-numpy ratio gate in tools/check_bench.py.
            "wall_s": wall_s,
            "cold_s": cold_s,
            # total kernel-dispatch count; the gate asserts pallas@4 does
            # not launch more than pallas@1 (one vmapped launch per group)
            "kernel_launches": sum(counts.values()),
        }
        if res.freshness_seconds:
            metrics[label]["freshness_mean_s"] = res.freshness_seconds["mean"]
            metrics[label]["freshness_max_s"] = res.freshness_seconds["max"]
        # per-session trace ledgers (RunResult.stats["traces"]): the cold
        # pass carries every trace+compile; the last warm pass must be
        # empty in steady state (pow2 bucketing -> pure cache hits). Kept
        # out of the gated payload — shape-bucket counts are informational.
        traces[label] = {
            "cold": res.stats.get("traces", {}),
            "warm_last": res2.stats.get("traces", {}),
        }
    payload = {
        "workload": "ci_workload (seed 0): 4000 rows x 4 cols, 8000 txn, "
                    "12 queries, n_rounds=4, Polynesia",
        "answers_checksum": int(np.int64(sum(a % (1 << 31) for a in answers))),
        "metrics": metrics,
    }
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {json_path}")
    traces_path = (json_path[:-5] if json_path.endswith(".json")
                   else json_path) + "_traces.json"
    with open(traces_path, "w") as f:
        json.dump({"traces": traces}, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {traces_path}")
    for combo, m in sorted(metrics.items()):
        print(f"ci_{combo},{m['wall_s'] * 1e6:.1f},"
              f"txn_tps={m['txn_tps']:.6e};ana_qps={m['ana_qps']:.6e};"
              f"launches={m['kernel_launches']}")


def main() -> None:
    from repro.kernels.common import use_compile_cache
    use_compile_cache()
    from benchmarks import (fig1_consistency_overhead, fig2_update_shipping,
                            fig3_breakdown, fig6_end_to_end,
                            fig7_update_propagation, fig8_consistency,
                            fig9_placement_sched, fig10_scaling_energy,
                            fig_elastic, fig_serve, lm_step)

    modules = [
        ("fig1", fig1_consistency_overhead),
        ("fig2", fig2_update_shipping),
        ("fig3", fig3_breakdown),
        ("fig6", fig6_end_to_end),
        ("fig7", fig7_update_propagation),
        ("fig8", fig8_consistency),
        ("fig9", fig9_placement_sched),
        ("fig10", fig10_scaling_energy),
        ("serve", fig_serve),
        ("elastic", fig_elastic),
        ("lm_step", lm_step),
    ]
    args = sys.argv[1:]
    json_path = "BENCH_ci.json"
    for a in [a for a in args if a.startswith("--")]:
        if a.startswith("--backend="):
            from repro.core.backend import set_default_backend
            try:
                set_default_backend(a.split("=", 1)[1])
            except (KeyError, ValueError) as e:
                sys.exit(f"{e.args[0]}; {USAGE}")
        elif a.startswith("--shards="):
            from repro.core.backend import set_default_n_shards
            try:
                set_default_n_shards(int(a.split("=", 1)[1]))
            except ValueError as e:
                sys.exit(f"{e}; {USAGE}")
        elif a.startswith("--timing="):
            from repro.core.timeline import set_default_timing
            try:
                set_default_timing(a.split("=", 1)[1])
            except ValueError as e:
                sys.exit(f"{e.args[0]}; {USAGE}")
        elif a.startswith("--json="):
            json_path = a.split("=", 1)[1]
        else:
            sys.exit(f"unknown option {a!r}; {USAGE}")
        args.remove(a)
    only = args[0] if args else None
    if only == "ci":
        ci_bench(json_path)
        return
    all_rows = []
    print("name,us_per_call,derived")
    for tag, mod in modules:
        if only and only != tag:
            continue
        t0 = time.perf_counter()
        rows = mod.run()
        dt = time.perf_counter() - t0
        print(f"# {tag} completed in {dt:.1f}s")
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}")
        all_rows += rows
    print(f"# total benchmark rows: {len(all_rows)}")


if __name__ == "__main__":
    main()
