"""Shared set-up of the benchmark's CPU tests: cells at 2^12 rows."""

from __future__ import annotations

import json

from chipbench import run
from chipbench.cells import load_json

ROWS = 1 << 12
CELLS = tuple(w["name"] for w in
              load_json(run.ROOT / "BENCHMARK.json")["workloads"])


def small(cell: str, backend: str = "numpy") -> dict:
    """Configuration overrides: the cell's system on ``backend`` with a
    2^12-row table."""
    config = run.load_cell(cell, run.ROOT / "BENCHMARK.json").config
    return {"n_rows": ROWS, "system": {**config["system"],
                                       "backend": backend}}


def run_small(cell: str, seed: int, capsys, seconds: float = 2.0,
              **kw) -> dict:
    """One run of ``cell`` at 2^12 rows on the CPU; its result line, with
    its standard error under ``"stderr"``."""
    kw.setdefault("overrides", small(cell))
    assert run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                     str(seconds), "--trace", "0"], require_chip=False,
                    **kw) == 0
    out, err = capsys.readouterr()
    return {**json.loads(out.strip().splitlines()[-1]), "stderr": err}
