"""A configuration, traffic mix and metric added as new files, with one new
entry each in BENCHMARK.json, are found by name and run: no file that is
already there changes. The new configuration here is the delta-store
update plane, so the harness drives that plane too."""

import json
import shutil

from chipbench import run
from chipbench.cells import load_cell
from chipbench.tests.helpers import ROWS

NEW_METRIC = '''
"""Queries answered per commit group in the window."""


def read(run):
    if not run.served.groups:
        return None
    return len(run.served.queries) / len(run.served.groups)
'''


def test_every_cell_loads():
    path = run.ROOT / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for w in bench["workloads"]:
        cell = load_cell(w["name"], path)
        assert cell.metrics["end_to_end"] and cell.metrics["per_layer"]
        names = [m["name"] for m, _ in cell.metrics["end_to_end"]]
        assert "setup_s" in names and len(names) >= 2


def test_new_cell_from_new_files(tmp_path, capsys):
    data = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(run.ROOT / "chipbench" / sub, data / sub)
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    config = json.loads(
        (data / "configs" / "ubench-eager.json").read_text())
    config.update(name="ubench-delta", n_rows=ROWS, n_cols=4)
    config["system"].update(backend="numpy", delta_store=True,
                            delta_capacity=64)
    (data / "configs" / "ubench-delta.json").write_text(json.dumps(config))
    traffic = json.loads((data / "traffic" / "eager.wi50.json").read_text())
    traffic.update(write_share=0.8, rate_qps=3.0, prefill_txns=2048)
    (data / "traffic" / "delta.wi80.json").write_text(json.dumps(traffic))
    (data / "metrics" / "queries_per_group.py").write_text(NEW_METRIC)
    bench["configs"].append({
        "name": "ubench-delta", "source": "https://arxiv.org/abs/2103.00798",
        "file": "chipbench/configs/ubench-delta.json", "reduced": [],
        "why": "a test deployment"})
    bench["workloads"].append({
        "name": "delta.wi80", "config": "ubench-delta",
        "traffic": "delta.wi80", "chips": 1, "why": "a test cell"})
    bench["end_to_end"].append({
        "name": "queries_per_group", "unit": "1", "better": "higher",
        "bound": 0.25, "source": "host_clock", "workloads": ["delta.wi80"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    assert run.main(["--workload", "delta.wi80", "--seed", "8",
                     "--seconds", "2", "--trace", "0"],
                    bench=tmp_path / "BENCHMARK.json",
                    require_chip=False) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["metrics"]["queries_per_group"]["value"] > 0
    assert {"query_p50_s", "setup_s"} <= set(result["metrics"])
