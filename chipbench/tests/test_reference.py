"""The plain reference answers as the program does, and the check fails
the faults and controls it exists to catch."""

import dataclasses

import numpy as np
import pytest

from chipbench import gen, run, serve
from chipbench.reference import Reference
from chipbench.tests.helpers import CELLS, ROWS, run_small, small


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_numpy_session(cell):
    """Every answer of an HTAPSession on the numpy backend, at each
    visibility point of the cell's traffic, equals the reference's."""
    seed = 99
    o = run.open_cell(cell, seed, require_chip=False,
                      overrides=small(cell))
    queries, due = run.window_traffic(seed, o.config, o.traffic, 4.0)
    loop = o.loop
    ref = Reference(gen.gen_table(seed, ROWS, 8, 32, 1 << 24), 1 << 24)
    for c in loop.chunks:
        ref.apply(c)
    for i in range(0, len(queries), 3):
        if loop.writes:
            loop.execute(loop.group * (i % 5 + 1))
            ref.apply(loop.chunks[-1])
        _, answers = loop.serve(queries[i:i + 3])
        assert answers == [ref.answer(q) for q in queries[i:i + 3]]


class Broken:
    """The program's session with one call broken underneath."""

    def __init__(self, session, fault):
        self.session, self.fault = session, fault

    def execute(self, chunk):
        if self.fault == "state_unchanged":
            return
        if self.fault == "half_batch":
            chunk = dataclasses.replace(
                chunk, **{f.name: getattr(chunk, f.name)[:len(chunk) // 2]
                          for f in dataclasses.fields(chunk)})
        self.session.execute(chunk)

    def flush_updates(self):
        self.session.flush_updates()

    def query_batch(self, queries):
        answers = self.session.query_batch(queries)
        if self.fault == "answer_altered":
            answers[0] += 1
        return answers


def broken(fault):
    def make(config, table):
        session, to_txns, to_queries = run.program_system(config, table)
        return Broken(session, fault), to_txns, to_queries
    return make


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, capsys):
    result = run_small(cell, 2**31 + 77, capsys)
    assert result["correct"] is True
    assert result["check"]["checked_answers"]["value"] > 0
    assert list(result)[-2] == "check"   # last but the test's "stderr"
    assert result["stderr"].strip().splitlines()[-1].startswith("check ")


@pytest.mark.parametrize("fault", [
    "state_unchanged", "half_batch", "answer_altered"])
def test_faults_come_out_not_correct(fault, capsys):
    result = run_small("eager.wi50", 31, capsys, make_system=broken(fault))
    assert result["correct"] is False
    assert result["check"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("seed", [41, 2**31 + 5])
def test_control_comes_out_not_correct(seed, capsys):
    """In the program's place in the check, the stale control fails it on
    the answers that the program gets right."""
    result = run_small("eager.wi50", seed, capsys, seconds=10.0,
                       control="stale")
    assert result["correct"] is False
    assert result["check"]["wrong_answers"]["value"] > 0
    assert "the program's own: wrong_answers 0," in result["stderr"]


def test_control_counts_every_checked_answer():
    """Every checked query of a batch is read stale, not the first alone:
    with every answer altered by the last chunk, the control gets each of
    them wrong."""
    seed = 17
    o = run.open_cell("eager.wi50", seed, require_chip=False,
                      overrides=small("eager.wi50"))
    loop = o.loop
    queries = gen.gen_queries(gen.rng_for(seed, 9), 6, 8, 1 << 24, 0.3, 0.0)
    records = []
    for k in (3, 3):
        # rewrite every aggregate column in every row: each answer moves
        loop.run_txns(loop.txns.take_writes([ROWS] * 8))
        start, answers = loop.serve(queries[len(records):len(records) + k])
        records += [serve.QueryRecord(len(records) + i, 0.0, start, 0.0,
                                      loop.executed, a, False)
                    for i, a in enumerate(answers)]
    served = serve.Served(t0=0.0, t_close=1.0, groups=[], queries=records,
                          n_due=len(records))
    check, own = run.verify(served, queries, loop.chunks, seed, o.config,
                            control="stale")
    assert own["wrong_answers"]["value"] == 0
    assert check["wrong_answers"]["value"] == len(records) == 6


def test_warm_up_flushes_every_shape():
    """Each warm-up flush touches exactly the planned columns, the widest
    with the planned number of writes, whatever the seed."""
    for seed in (3, 2**31 + 9):
        o = run.open_cell("eager.wi50", seed, require_chip=False,
                          overrides=small("eager.wi50"))
        shaped = o.loop.chunks[-len(serve.WARMUP_FLUSHES):]
        for chunk, (touched, widest) in zip(shaped, serve.WARMUP_FLUSHES):
            assert (chunk.op == gen.OP_MODIFY).all()
            counts = np.bincount(chunk.col, minlength=8)
            assert (counts > 0).sum() == touched
            assert counts.max() == widest
