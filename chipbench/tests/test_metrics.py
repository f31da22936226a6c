"""Rate and percentile arithmetic of the end-to-end readers: every query
due in the window counts, answered or not."""

import math

import pytest

from chipbench import run, serve, stats
from chipbench.cells import load_reader

METRICS = run.ROOT / "chipbench"


def served(latencies, n_due, groups=()):
    qs = [serve.QueryRecord(i, due=10.0, start=10.0, done=10.0 + lat,
                            visible=0, answer=0, drained=False)
          for i, lat in enumerate(latencies)]
    return serve.Served(t0=10.0, t_close=30.0, groups=list(groups),
                        queries=qs, n_due=n_due)


def make_run(s, spans=(), compile_intervals=()):
    return run.Run(cell=None, setup_s=12.5, served=s, spans=list(spans),
                   compile_intervals=list(compile_intervals), trace=None,
                   n_rows=1 << 12, device_kind="cpu")


def read(name, r):
    return load_reader(name, METRICS)(r)


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile(vals, 90) == 90
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 50) is None


def test_unanswered_queries_count_in_the_tail():
    # 10 due, 8 answered at 1..8 s: the two missing are the slowest
    r = make_run(served([float(i) for i in range(1, 9)], n_due=10))
    assert read("query_p50_s", r) == 5.0
    assert read("query_p90_s", r) == math.inf
    r = make_run(served([float(i) for i in range(1, 11)], n_due=10))
    assert read("query_p90_s", r) == 9.0


def test_txn_rate_and_tail_over_the_whole_window():
    groups = [(10.0 + i, 10.5 + i, 64) for i in range(19)] + [(29, 30, 64)]
    r = make_run(served([1.0], 1, groups))
    assert read("txn_per_s", r) == pytest.approx(20 * 64 / 20.0)
    assert read("txn_p95_s", r) == 0.5
    assert read("txn_p95_s", make_run(served([1.0], 1))) is None
    assert read("setup_s", r) == 12.5


def test_layer_readers_split_time_by_span():
    spans = [("warmup", 0, 9), ("execute", 11, 12), ("execute", 13, 13.5),
             ("flush", 14, 16), ("query_batch", 16, 17),
             ("execute", 31, 32)]
    r = make_run(served([2.0, 3.0], 2), spans,
                 compile_intervals=[(11.5, 12.5), (11.8, 12.2), (15, 16.5),
                                    (5, 6)])
    assert read("execute_ms_per_group", r) == pytest.approx(750.0)
    assert read("flush_ms_per_batch", r) == pytest.approx(2000.0)
    assert read("query_ms_per_query", r) == pytest.approx(500.0)
    # nested compile events count once; warm-up and drain stay outside
    assert read("compile_s.execute", r) == pytest.approx(0.5)
    assert read("compile_s.query", r) == pytest.approx(1.5)
    assert read("query_wait_ms", r) == 0.0
    assert read("scan_roofline", r) is None
    assert read("device_idle.htap", r) is None
