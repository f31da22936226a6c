"""The trace reduction, on two profiler traces recorded on a TPU v5e by
``--trace 1`` runs of eager.wi50 and eager.ana (2^24 rows)."""

from pathlib import Path

import pytest

from chipbench import trace
from chipbench.cells import load_reader
from chipbench.run import ROOT

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def eager():
    return trace.reduce(DATA / "eager_wi50.xplane.pb.gz")


@pytest.fixture(scope="module")
def ana():
    return trace.reduce(DATA / "eager_ana.xplane.pb.gz")


def test_program_names():
    assert trace.program_name(
        "jit__join_scan_pallas(3487446927757424086)") == "join_scan_pallas"
    assert trace.program_name(
        "jit_scan_filter_agg_exact_kernel(2)") == "scan_filter_agg_exact_kernel"
    assert trace.program_name(
        "jit__join_group_pallas_body(5)") == "join_group_kernel"
    assert trace.program_name(
        "jit__scan_group_kernel_body(5)") == "scan_group_kernel"


def test_window_and_busy_share(eager, ana):
    for r in (eager, ana):
        assert 0 < r["busy_s"] < r["window_s"]
        assert len(r["device_ops"]) <= trace.TOP
        assert len(r["idle_gaps"]) <= trace.TOP
    # the window is the chipbench.traced annotation, not the device's
    # first and last operation
    assert eager["window_s"] == pytest.approx(2.804531218)
    assert eager["busy_s"] == pytest.approx(0.588374478)
    assert ana["window_s"] == pytest.approx(4.464289815)


def test_device_ops_and_idle_gaps_are_named(eager, ana):
    assert eager["device_ops"][0][0] == "scan_filter_agg_exact_kernel"
    assert {n for n, _ in ana["device_ops"]} == {
        "join_scan_pallas", "scan_filter_agg_exact_kernel"}
    # the eager flush re-encodes on the host while the device idles
    assert eager["idle_gaps"][0][0] == "flush"
    assert eager["idle_gaps"][0][1] > 1.0
    labels = {n for n, _ in ana["idle_gaps"]}
    assert labels <= {"wait", "query_batch", "between_spans"}
    gaps = sorted((s for _, s in eager["idle_gaps"]), reverse=True)
    assert [s for _, s in eager["idle_gaps"]] == gaps


@pytest.mark.parametrize("which,low,high", [
    ("eager", 0.05, 0.5), ("ana", 3.0, 15.0)])
def test_scan_roofline_share(which, low, high, eager, ana):
    """The share is a lower bound on bytes over device time: never above
    100%, and on these traces the eager scans (4096-entry dictionaries)
    sit far below the 32-entry ones of eager.ana."""

    class Run:
        trace = {"eager": eager, "ana": ana}[which]
        device_kind = "TPU v5 lite"
        n_rows = 1 << 24

    share = load_reader("scan_roofline", ROOT / "chipbench")(Run)
    assert low < share < high <= 100


def test_unknown_device_kind_is_an_error(ana):
    from chipbench.roofline import peaks
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")
