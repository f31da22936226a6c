"""Chip benchmark of the Polynesia HTAP session: one cell, one run.

    python3 -m chipbench.run --workload eager.wi50 --seed 7 --seconds 45 \\
        --trace 0

Run from the root of a checkout. The cell (an entry of ``BENCHMARK.json``'s
``workloads``) names a configuration (``chipbench/configs/``) and a traffic
mix (``chipbench/traffic/``). One process generates the table and traffic
from ``--seed``, opens an ``HTAPSession`` on the configuration's spec,
warms it up, serves the traffic for ``--seconds`` (``chipbench/serve.py``),
checks a seeded sample of the answers against the plain reference
(``chipbench/reference.py``), and prints one JSON line last on stdout.

With ``--trace 0`` the line holds the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from spans, compile events and a
profiler trace of part of the window. Each metric is computed by its own
reader, ``chipbench/metrics/<name>.py``.

The run fails (exit code 1, no result line) when JAX finds no TPU, when the
kernels would not run compiled, when the chip has fewer devices than the
cell asks for, or when the program is not next to ``chipbench/``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import gen, serve  # noqa: E402
from chipbench.cells import Cell, load_cell  # noqa: E402
from chipbench.reference import Reference  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench" / "trace"
# Answers of the window checked against the reference, drawn from the seed.
CHECK_SAMPLE = 48
# Part of the window traced with --trace 1, as fractions of it.
TRACE_FROM, TRACE_TO = 0.3, 0.6


class BenchError(RuntimeError):
    pass


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rate", type=float, default=None,
                   help="query rate in place of the traffic's, for the "
                   "knee sweep (PERF.md); the benchmark never passes it")
    return p.parse_args(argv)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    setup_s: float
    served: serve.Served
    spans: list
    compile_intervals: list
    trace: dict | None
    n_rows: int
    device_kind: str


def program_system(config: dict, table: np.ndarray):
    """The system under test: an ``HTAPSession`` on the configuration's
    spec, with every field it depends on set, and the converters from the
    generated traffic to the program's own types."""
    from repro.core import engine
    from repro.core.htap import HTAPSession, SystemSpec
    from repro.core.schema import UpdateStream

    s = dict(config["system"])
    preset = s.pop("preset")
    spec = getattr(SystemSpec, preset)(**s)
    session = HTAPSession(spec, table)

    def to_txns(t: gen.Txns):
        return UpdateStream(t.thread_id, t.commit_id, t.op, t.row, t.col,
                            t.value)

    ids = iter(range(1 << 62))

    def to_queries(qs):
        return [engine.Query(next(ids), q.filter_col, q.lo, q.hi, q.agg_col,
                             q.join_col) for q in qs]

    return session, to_txns, to_queries


def warm_queries(seed: int, config: dict, traffic: dict):
    """Warm-up queries: a join-free and a join query, then 2 and 4 queries
    of one column set, without and with a join, for the query-count
    buckets the window can meet."""
    n_cols, domain = config["n_cols"], config["value_domain"]
    sel = traffic["selectivity"]
    rng = gen.rng_for(seed, 3)
    out = (gen.gen_queries(rng, 1, n_cols, domain, sel, 0.0)
           + gen.gen_queries(rng, 1, n_cols, domain, sel, 1.0))
    for k in (2, 4):
        for join in (0.0, 1.0):
            [q] = gen.gen_queries(rng, 1, n_cols, domain, sel, join)
            for o in gen.gen_queries(rng, k, n_cols, domain, sel, 0.0):
                out.append(dataclasses.replace(q, lo=o.lo, hi=o.hi))
    return out


def check_chip(chips: int):
    import jax

    from repro.kernels import common

    backend = jax.default_backend()
    if backend != "tpu":
        raise BenchError(f"JAX found no TPU (default backend {backend!r}); "
                         "the benchmark runs on the chip only")
    mode = common.kernel_mode()
    if mode != "compiled":
        raise BenchError(f"kernels resolve to {mode!r} mode; the benchmark "
                         "needs them compiled")
    if len(jax.devices()) < chips:
        raise BenchError(f"the cell needs {chips} chip(s); JAX sees "
                         f"{len(jax.devices())}")


def verify(served: serve.Served, queries, chunks, seed: int,
           config: dict, control: str | None = None,
           sample: int = CHECK_SAMPLE) -> tuple[dict, dict]:
    """Compare a seeded sample of the window's answers with the reference
    at each answer's visibility point: the table as generated, with every
    chunk of transactions executed before its batch applied in order.

    Returns the numbers compared, each with its limit, and the program's
    own. With ``control`` the control stands in the program's place
    (``chipbench/control.py``): ``stale`` answers each checked query
    without the last chunk executed before its batch, so the first are the
    control's numbers and must fail."""
    records = served.queries
    rng = gen.rng_for(seed, 5)
    pick = rng.choice(len(records), size=min(sample, len(records)),
                      replace=False) if records else []
    picked = sorted((records[i] for i in pick), key=lambda r: r.visible)
    table = gen.gen_table(seed, config["n_rows"], config["n_cols"],
                          config["distinct_per_column"],
                          config["value_domain"])
    ref = Reference(table, config["value_domain"])
    del table
    ends = np.cumsum([len(c) for c in chunks])
    applied, wrong, control_wrong = 0, 0, 0
    for visible, batch in itertools.groupby(picked, key=lambda r: r.visible):
        batch = list(batch)
        last = int(np.searchsorted(ends, visible))
        if last >= len(chunks) or ends[last] != visible:
            raise BenchError(f"a batch saw {visible} transactions, which "
                             "ends no executed chunk")
        for c in chunks[applied:last]:
            ref.apply(c)
        stale = ([ref.answer(queries[r.index]) for r in batch]
                 if control == "stale" else None)
        ref.apply(chunks[last])
        applied = last + 1
        want = [ref.answer(queries[r.index]) for r in batch]
        wrong += sum(w != r.answer for w, r in zip(want, batch))
        if stale is not None:
            control_wrong += sum(w != a for w, a in zip(want, stale))

    def numbers(n_wrong):
        return {"wrong_answers": {"value": n_wrong, "limit": 0},
                "unanswered": {"value": served.n_due - len(records),
                               "limit": 0},
                "checked_answers": {"value": len(picked),
                                    "min": min(1, served.n_due)}}

    own = numbers(wrong)
    return (numbers(control_wrong) if control else own), own


def check_passed(check: dict) -> bool:
    return all(v["value"] <= v["limit"] if "limit" in v
               else v["value"] >= v["min"] for v in check.values())


def txn_stream(seed: int, config: dict, traffic: dict) -> gen.TxnStream:
    return gen.TxnStream(seed, config["n_rows"], config["n_cols"],
                         config["value_domain"], traffic["write_share"],
                         config["threads"])


class Tracer:
    """A profiler trace of part of the window, started and stopped by the
    served loop between two operations. While it runs, every span is also
    a trace annotation, and ``chipbench.traced`` marks the window."""

    def __init__(self, spans: serve.Spans, seconds: float):
        self.spans = spans
        self.start_at, self.stop_at = TRACE_FROM * seconds, TRACE_TO * seconds
        self._window = None

    def start(self):
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.spans.annotate = True
        self._window = jax.profiler.TraceAnnotation("chipbench.traced")
        self._window.__enter__()

    def stop(self):
        import jax

        self._window.__exit__(None, None, None)
        self.spans.annotate = False
        jax.profiler.stop_trace()

    def reduce(self) -> dict | None:
        """The trace's device numbers (``chipbench/trace.py``); the trace
        itself is deleted."""
        from chipbench import trace

        files = glob.glob(str(TRACE_DIR / "plugins" / "profile" / "*" /
                              "*.xplane.pb"))
        out = trace.reduce(files[0]) if files else None
        shutil.rmtree(TRACE_DIR.parent, ignore_errors=True)
        return out


@dataclasses.dataclass
class Opened:
    """A cell's session, warmed up and ready for its window."""

    cell: Cell
    config: dict
    traffic: dict
    loop: serve.Loop
    clock: serve.CompileClock
    spans: serve.Spans


def open_cell(workload: str, seed: int, *, bench: Path | None = None,
              require_chip: bool = True, overrides: dict | None = None,
              make_system=program_system) -> Opened:
    """Load the cell, generate its table, open the system and warm it up."""
    # every field the session depends on comes from the configuration;
    # no REPRO_* default of the program may change what is measured
    for k in [k for k in os.environ if k.startswith("REPRO_")]:
        del os.environ[k]
    bench = Path(bench or ROOT / "BENCHMARK.json")
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the program (src/repro) is not next to "
                         f"{ROOT / 'chipbench'}")
    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(workload, bench)
    config = {**cell.config, **(overrides or {})}
    traffic = cell.traffic

    import jax

    from repro.kernels import common

    if require_chip:
        check_chip(cell.chips)
    common.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = serve.CompileClock()
    spans = serve.Spans()

    writes = traffic["write_share"] > 0
    table = gen.gen_table(seed, config["n_rows"], config["n_cols"],
                          config["distinct_per_column"],
                          config["value_domain"])
    with spans("open"):
        session, to_txns, to_queries = make_system(config, table)
    del table
    loop = serve.Loop(session, to_txns, to_queries,
                      txn_stream(seed, config, traffic), writes,
                      traffic["group_txns"], spans)
    loop.warm_up(traffic["prefill_txns"],
                 warm_queries(seed, config, traffic))
    return Opened(cell, config, traffic, loop, clock, spans)


def window_traffic(seed: int, config: dict, traffic: dict, seconds: float,
                   rate: float | None = None):
    """The window's queries and their due times (seconds from its start)."""
    due = gen.arrival_times(traffic["rate_qps"] if rate is None else rate,
                            seconds)
    queries = gen.gen_queries(gen.rng_for(seed, 2), len(due),
                              config["n_cols"], config["value_domain"],
                              traffic["selectivity"], traffic["join_share"])
    return queries, due


def served_summary(served: serve.Served, seconds: float) -> dict:
    """How the window went, for the knee sweep: queries due and still
    queued at the close, and the mean wait of the queries due in each half
    of the window (the backlog holds where the second does not outgrow
    the first by more than a batch)."""
    mid = served.t0 + seconds / 2
    halves = [[q.start - q.due for q in served.queries
               if (q.due < mid) == first] for first in (True, False)]
    starts = sorted({q.start for q in served.queries if not q.drained})
    batch_s = [q.done - q.start for q in served.queries]
    return {"due": served.n_due, "batches": len(starts),
            "queued_at_close": sum(q.drained for q in served.queries),
            "groups": len(served.groups),
            "wait_first_half_s": (sum(halves[0]) / len(halves[0])
                                  if halves[0] else None),
            "wait_second_half_s": (sum(halves[1]) / len(halves[1])
                                   if halves[1] else None),
            "mean_batch_s": (sum(batch_s) / len(batch_s)
                             if batch_s else None)}


def main(argv=None, *, bench: Path | None = None, require_chip: bool = True,
         overrides: dict | None = None, make_system=program_system,
         control: str | None = None) -> int:
    """One run of one cell. The keyword arguments serve the tests and the
    control runs: ``require_chip=False`` skips the look for a TPU,
    ``overrides`` replaces configuration keys (a smaller table, another
    backend), ``make_system`` puts another system in the program's place,
    and ``control`` puts the control in its place in the check
    (``chipbench/control.py``)."""
    args = parse_args(argv)
    seed = args.seed
    o = open_cell(args.workload, seed, bench=bench,
                  require_chip=require_chip, overrides=overrides,
                  make_system=make_system)
    cell, config, traffic, loop, spans = (o.cell, o.config, o.traffic,
                                          o.loop, o.spans)
    queries, due = window_traffic(seed, config, traffic, args.seconds,
                                  args.rate)
    setup_s = time.perf_counter() - T_START

    import jax

    tracer = Tracer(spans, args.seconds) if args.trace else None
    served = serve.run_window(loop, queries, due, args.seconds, tracer)

    devices = jax.devices()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])
    trace = tracer.reduce() if tracer else None
    run = Run(cell=cell, setup_s=setup_s, served=served, spans=spans.spans,
              compile_intervals=o.clock.intervals, trace=trace,
              n_rows=config["n_rows"], device_kind=devices[0].device_kind)
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry, reader in cell.metrics[kind]:
        value = reader(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    # free the program's state before the reference runs
    clock, chunks = o.clock, loop.chunks
    del o, loop
    gc.collect()
    setup_spans = {n: b - a for n, a, b in spans.spans
                   if n in ("open", "warmup")}
    print(f"set-up {setup_s!r} s, of which {setup_spans}", file=sys.stderr)
    in_window = collections.Counter(
        name for (a, b), name in zip(clock.intervals, clock.names)
        if a >= served.t0 and b <= served.t_close)
    print(f"compile events in the window: {dict(in_window)}",
          file=sys.stderr)
    print(f"served: {json.dumps(served_summary(served, args.seconds))}",
          file=sys.stderr)
    check, own = verify(served, queries, chunks, seed, config, control)
    del chunks
    correct = check_passed(check)
    if control:
        print(f"control {control} in the program's place; the program's "
              f"own: " + ", ".join(f"{k} {v['value']}"
                                   for k, v in own.items()), file=sys.stderr)
    for name, v in check.items():
        bound = (f"limit {v['limit']}" if "limit" in v
                 else f"at least {v['min']}")
        print(f"check {name}: {v['value']} ({bound})", file=sys.stderr)
    result = {
        "correct": correct,
        "attempted": served.n_due + len(served.groups),
        "failed": check["unanswered"]["value"]
        + check["wrong_answers"]["value"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": int(peak)},
    }
    if trace is not None:
        result["device"]["busy_s"] = trace["busy_s"]
        result["device"]["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["check"] = check
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # any failure: say why, print no result
        import traceback
        traceback.print_exc()
        print(f"chipbench: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
