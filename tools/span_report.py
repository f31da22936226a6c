"""Where a benchmark cell's time goes, read from the session's own spans.

    python3 tools/span_report.py --workload eager.wi50 --seed 7 --seconds 51
    python3 tools/span_report.py --workload eager.wi50 --seed 7 \\
        --seconds 51 --recorder-cost
    python3 tools/span_report.py --workload mesh4.wi50 --seed 7 \\
        --seconds 51 --bench <dir>/BENCHMARK.json

Run from the root of a checkout, on the chip. The first form opens a cell
of the chip benchmark (``chipbench/``) as ``chipbench.run`` does, turns the
session's span recorder on after the warm-up (``CostLog.record_spans``),
serves the window with a profiler trace of its middle, checks the answers
against the reference and prints one JSON line: the per-layer numbers the
program's spans and counters give over the window (``metrics``), how the
window was served and the benchmark's own spans in it, how much
of the benchmark's own ``flush`` and ``query_batch`` spans the program's
spans cover, each span name's count and total and self seconds, the spans
recorded a second and what one costs, the device idle time split by the
innermost ``repro.*`` span open over it, the longest idle gaps labelled by
the innermost span of either kind, and each program's runs and seconds in
the trace.

The second form is one ``--trace 0`` run of ``chipbench.run`` with the
recorder on from the session's start: set beside a plain ``--trace 0``
run of the same seed, it is what recording costs. The third reads a cell
from another benchmark file, with its own ``chipbench/`` beside it.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
import time
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from chipbench import run, serve, trace  # noqa: E402
from chipbench.stats import union  # noqa: E402

# The benchmark's spans whose time the program's spans should account for.
COVERS = {"flush": ("ship_batch",), "query_batch": ("snapshot", "ana")}
NO_SPAN = "no_program_span"


def recording_system(config, table):
    """``chipbench.run.program_system`` with the span recorder on."""
    session, to_txns, to_queries = run.program_system(config, table)
    session.cost.record_spans()
    return session, to_txns, to_queries


def span_totals(spans, t0: float, t1: float) -> dict:
    """name -> [count, seconds, self seconds (less its child spans)] of
    the recorded ``spans`` that lie in ``[t0, t1]``."""
    child_s = {}
    for s in spans:
        if s.parent >= 0:
            child_s[s.parent] = child_s.get(s.parent, 0.0) + s.t1 - s.t0
    out = {}
    for i, s in enumerate(spans):
        if s.t0 >= t0 and s.t1 <= t1:
            row = out.setdefault(s.name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += s.t1 - s.t0
            row[2] += s.t1 - s.t0 - child_s.get(i, 0.0)
    return out


def layer_metrics(totals: dict, n_groups: int, traces: tuple[int, int],
                  reencodes: tuple[int, int] = (0, 0)) -> dict:
    """The per-layer numbers of a window from its span totals
    (`span_totals`): milliseconds per commit group, per ship batch and per
    query group, the kernel traces the window added, and, next to the
    re-encode's milliseconds, the columns whose stage 3 the window ran on
    the device and on the host (``reencodes``) with the device's share of
    them in percent."""

    def count(name):
        return totals.get(name, [0, 0.0, 0.0])[0]

    def ms(name):
        return 1e3 * totals.get(name, [0, 0.0, 0.0])[1]

    ships, groups = count("ship_batch"), count("ana")
    out = {"retraces_in_window": traces[1] - traces[0]}
    if n_groups:
        out["rowstore_ms_per_group"] = ms("txn") / n_groups
    if ships:
        device, host = reencodes
        out.update(ship_ms_per_ship=ms("ship_batch") / ships,
                   reencode_ms_per_ship=ms("reencode") / ships,
                   reencodes_device=device, reencodes_host=host,
                   reencode_device_share=(100.0 * device / (device + host)
                                          if device + host else 0.0),
                   stages_ms_per_ship=ms("stages") / ships)
    if groups:
        out.update(snapshot_ms_per_group=ms("snapshot") / groups,
                   scan_ms_per_group=ms("scan") / groups,
                   query_glue_ms_per_group=1e3 * totals["ana"][2] / groups)
    return out


def bench_totals(bench_spans) -> dict:
    """name -> [count, seconds] of the benchmark's own spans."""
    out = {}
    for name, a, b in bench_spans:
        row = out.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += b - a
    return out


def coverage(bench_spans, spans) -> dict:
    """Share of each benchmark span's time that the named program spans
    inside it take."""
    out = {}
    for outer, inner in COVERS.items():
        outs = [(a, b) for n, a, b in bench_spans if n == outer]
        total = sum(b - a for a, b in outs)
        covered = sum(s.t1 - s.t0 for s in spans if s.name in inner
                      and any(a <= s.t0 and s.t1 <= b for a, b in outs))
        if total:
            out[outer] = covered / total
    return out


def innermost(spans, t):
    """Name of the shortest span open at ``t``, or None."""
    open_ = [(b - a, n) for n, a, b in spans if a <= t <= b]
    return min(open_)[1] if open_ else None


def reduce_trace(data) -> dict | None:
    """The traced window's device numbers, with its idle time split by the
    program's spans.

    ``chipbench.trace.reduce`` reduces the trace as the benchmark does,
    but shown every ``repro.*`` host annotation as a benchmark span too,
    so each idle gap is labelled by the innermost span of either kind
    open at its middle (a program span keeps its ``repro.`` prefix). Adds
    ``idle_s`` and ``idle_s_by_program_span``: the idle seconds of the
    first busy device under each innermost ``repro.*`` span (``NO_SPAN``
    for none), and ``program_calls``: each program's runs in the traced
    window and their seconds, ``[calls, seconds]``. None when no device
    ran anything."""
    pd = data if hasattr(data, "planes") else trace.load(data)
    host = [(e.name, e.start_ns, e.end_ns) for plane in pd.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events
            if e.name.startswith((trace.PREFIX, "repro."))]
    devices = sorted((p for p in pd.planes if trace._is_device(p.name)),
                     key=lambda p: p.name)
    out = trace.reduce(types.SimpleNamespace(planes=devices + [
        types.SimpleNamespace(name="/host:spans", lines=[
            types.SimpleNamespace(name="spans", events=[
                types.SimpleNamespace(
                    name=n if n.startswith(trace.PREFIX)
                    else trace.PREFIX + n, start_ns=a, end_ns=b)
                for n, a, b in host])])]))
    window = [(a, b) for n, a, b in host if n == trace.WINDOW]
    if out is None or not window:
        return None
    lo, hi = window[0]
    # the first device that ran something, as for the benchmark's gaps
    busy = next(b for b in (union(trace._clip(
        [(e.start_ns, e.end_ns) for line in p.lines
         if line.name == "XLA Ops" for e in line.events], lo, hi))
        for p in devices) if b)
    gaps = [(a, b) for (_, a), (b, _) in zip([(lo, lo)] + busy,
                                             busy + [(hi, hi)]) if b > a]
    program = [s for s in host if s[0].startswith("repro.")]
    by_span = {}
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {x for _, a, b in program for x in (a, b)
                                  if g0 < x < g1})
        for x, y in zip(cuts, cuts[1:]):
            name = innermost(program, (x + y) / 2) or NO_SPAN
            by_span[name] = by_span.get(name, 0) + (y - x) / 1e9
    calls = {}
    for name, seconds in out["programs"]:
        c = calls.setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += seconds
    return {**out, "idle_s": sum(b - a for a, b in gaps) / 1e9,
            "idle_s_by_program_span": by_span, "program_calls": calls}


def span_cost_s(n: int = 10000) -> float:
    """Seconds one recorded span costs on this host with no profiler
    trace running: the recorder's cost per span."""
    from repro.core.hwmodel import CostLog

    log = CostLog()
    log.record_spans()
    t = time.perf_counter()
    for _ in range(n):
        with log.span("cost"):
            pass
    return (time.perf_counter() - t) / n


class Tracer(run.Tracer):
    """The benchmark's profiler window, its trace also reduced here."""

    def reduce(self) -> dict | None:
        files = glob.glob(str(run.TRACE_DIR / "plugins" / "profile" / "*" /
                              "*.xplane.pb"))
        self.program = reduce_trace(files[0]) if files else None
        return super().reduce()


def report(workload: str, seed: int, seconds: float, *,
           require_chip: bool = True, overrides: dict | None = None,
           bench: Path | None = None) -> dict:
    """One window of ``workload`` with the span recorder on (see the
    module docstring); ``bench`` is the benchmark file that names the
    cell, ``BENCHMARK.json`` by default."""
    o = run.open_cell(workload, seed, require_chip=require_chip,
                      overrides=overrides, bench=bench)
    loop, session = o.loop, o.loop.session
    queries, due = run.window_traffic(seed, o.config, o.traffic, seconds)
    session.cost.record_spans()
    counters = {"open": session.counters()}
    t_end = time.perf_counter() + seconds
    window_serve = loop.serve

    def serve_(qs):
        # the queries still due at the close are served after it
        if "close" not in counters and time.perf_counter() >= t_end:
            counters["close"] = session.counters()
        return window_serve(qs)

    loop.serve = serve_
    tracer = Tracer(o.spans, seconds)
    served = serve.run_window(loop, queries, due, seconds, tracer)
    counters.setdefault("close", session.counters())
    tracer.reduce()
    device = tracer.program
    t0, t1 = served.t0, served.t_close
    totals = span_totals(session.cost.spans, t0, t1)
    bench = [(n, a, b) for n, a, b in o.spans.spans if a >= t0 and b <= t1]

    def added(name):
        return (counters["close"].get(name, 0)
                - counters["open"].get(name, 0))

    out = {"metrics": layer_metrics(totals, len(served.groups),
                                    (counters["open"]["kernel_traces"],
                                     counters["close"]["kernel_traces"]),
                                    (added("reencodes_device"),
                                     added("reencodes_host"))),
           "covers": coverage(bench, session.cost.spans),
           "spans": totals,
           "counters": counters,
           "spans_per_s": sum(c for c, _, _ in totals.values()) / (t1 - t0),
           "span_cost_us": 1e6 * span_cost_s(),
           "served": {**run.served_summary(served, seconds),
                      "txns": sum(n for _, _, n in served.groups),
                      "window_s": t1 - t0},
           "bench_spans": bench_totals(bench)}
    if device is not None:
        idle = device["idle_s"]
        out["metrics"]["idle_unattributed"] = (
            100.0 * device["idle_s_by_program_span"].get(NO_SPAN, 0.0) / idle
            if idle else 0.0)
        out["device"] = device
    check, _ = run.verify(served, queries, loop.chunks, seed, o.config)
    out["correct"] = run.check_passed(check)
    out["check"] = check
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--recorder-cost", action="store_true",
                   help="one --trace 0 benchmark run, recorder on")
    p.add_argument("--bench", type=Path, default=None,
                   help="the benchmark file naming the cell, with its "
                   "chipbench/ beside it (default: BENCHMARK.json)")
    args = p.parse_args(argv)
    if args.recorder_cost:
        return run.main(["--workload", args.workload, "--seed",
                         str(args.seed), "--seconds", str(args.seconds),
                         "--trace", "0"], make_system=recording_system,
                        bench=args.bench)
    print(json.dumps(report(args.workload, args.seed, args.seconds,
                            bench=args.bench)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
