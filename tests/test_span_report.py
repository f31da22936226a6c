"""tools/span_report.py: a benchmark cell's window read from the session's
own spans, and the device idle time split by them."""

import importlib.util
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "span_report", ROOT / "tools" / "span_report.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def xspace(host, ops):
    """A trace with one host plane of (name, start, end) annotations and
    one device plane of (start, end) operations, in nanoseconds."""
    def events(items):
        return [types.SimpleNamespace(name=n, start_ns=a, end_ns=b)
                for n, a, b in items]

    def plane(name, lines):
        return types.SimpleNamespace(name=name, lines=[
            types.SimpleNamespace(name=n, events=events(e))
            for n, e in lines])

    return types.SimpleNamespace(planes=[
        plane("/host:CPU", [("python", host)]),
        plane("/device:TPU:0", [("XLA Ops", [("op", a, b) for a, b in ops])]),
    ])


def test_idle_time_is_split_by_the_innermost_program_span(tool):
    s = int(1e9)
    trace = xspace(
        [("chipbench.traced", 0, 10 * s),
         ("chipbench.flush", 1 * s, 6 * s),
         ("repro.ship_batch", 1 * s, 6 * s),
         ("repro.apply", 2 * s, 5 * s),
         ("repro.reencode", 3 * s, 4 * s),
         ("chipbench.query_batch", 7 * s, 9 * s)],
        ops=[(0, 1 * s), (6 * s, 7 * s), (9 * s, 10 * s)])
    out = tool.reduce_trace(trace)
    assert out["window_s"] == 10 and out["idle_s"] == 7
    # a gap inside repro.reencode inside chipbench.flush is labelled by
    # the program span; one under no program span by the benchmark's
    assert out["idle_gaps"] == [("repro.reencode", 5.0),
                                ("query_batch", 2.0)]
    assert out["idle_s_by_program_span"] == {
        "repro.ship_batch": 2.0, "repro.apply": 2.0, "repro.reencode": 1.0,
        tool.NO_SPAN: 2.0}


def test_a_trace_without_device_work_gives_nothing(tool):
    assert tool.reduce_trace(xspace([("chipbench.traced", 0, 10)], [])) \
        is None


def test_window_report_on_the_cpu(tool, process_state):
    """The report of a short window at 2^12 rows: every per-layer number,
    the program spans inside the benchmark's, and correct answers."""
    from chipbench.tests.helpers import small

    out = tool.report("eager.wi50", 2**31 + 3, 3.0, require_chip=False,
                      overrides=small("eager.wi50", backend="pallas"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {
        "rowstore_ms_per_group", "ship_ms_per_ship", "reencode_ms_per_ship",
        "reencodes_device", "reencodes_host", "reencode_device_share",
        "stages_ms_per_ship", "snapshot_ms_per_group", "scan_ms_per_group",
        "query_glue_ms_per_group", "retraces_in_window"}
    assert all(v >= 0 for v in out["metrics"].values())
    # the eager plane's replica is on the device: every column of the
    # window's ships re-encoded there
    metrics = out["metrics"]
    assert metrics["reencodes_device"] > 0 == metrics["reencodes_host"]
    assert metrics["reencode_device_share"] == 100.0
    # the window's one batch is due at its start, when the warm-up has
    # left nothing to flush; its queries run inside snapshot and ana spans
    assert out["covers"]["flush"] == 0
    assert 0 < out["covers"]["query_batch"] <= 1
    spans = out["spans"]
    assert spans["ship_batch"][0] == (out["counters"]["close"]["ships"]
                                      - out["counters"]["open"]["ships"])
    for name, (count, total, own) in spans.items():
        assert count > 0 and 0 <= own <= total + 1e-9
    # how the window was served, and the benchmark's own spans in it
    served = out["served"]
    assert served["txns"] == 64 * served["groups"] > 0
    assert out["bench_spans"]["execute"][0] == served["groups"]


@pytest.mark.parametrize("name", ["eager_wi50", "eager_ana"])
def test_a_recorded_chip_trace_reduces_as_the_benchmark_does(tool, name):
    """On a chip trace recorded without program spans the report gives the
    benchmark's own reduction, and all its idle time under no span."""
    from chipbench import trace

    path = ROOT / "chipbench" / "tests" / "data" / f"{name}.xplane.pb.gz"
    want, got = trace.reduce(path), tool.reduce_trace(path)
    assert {k: got[k] for k in want} == want
    idle = want["window_s"] - want["busy_s"]
    assert got["idle_s"] == pytest.approx(idle, rel=1e-9)
    assert got["idle_s_by_program_span"] == {
        tool.NO_SPAN: pytest.approx(idle, rel=1e-9)}
    # each program's runs in the window, counted
    calls = got["program_calls"]
    assert sum(c for c, _ in calls.values()) == len(want["programs"])
    for name, total in want["device_ops"]:
        assert calls[name][1] == pytest.approx(total, rel=1e-9)
