"""Wall-time spans and counters of `HTAPSession` (core/hwmodel.py).

With `CostLog.record_spans()` every timeline node of a session is a span
named by its kind, holding the sub-spans of where its work happens: a ship
batch its drain, ship, dictionary stages and per-column applies, an apply
its stage-3 re-encode and Phase-2 swap, a query group its scans. The
properties: the spans nest by parent and by time, their counts match the
session's counters, recording changes no answer, no statistic and no
checkpoint, and while recording is off nothing is recorded or annotated.
"""

import collections
import math

import jax
import numpy as np
import pytest

from repro.checkpoint import load_arrays
from repro.core import engine, schema
from repro.core.session import HTAPSession, SystemSpec
from repro.core.workload import split_queries, split_stream
from repro.kernels.common import kernel_trace_counts

ROWS = 1 << 12
N_ROUNDS = 3
# Every field the spans depend on pinned, so no REPRO_* default (the delta
# plane, a sharded backend) changes what the properties hold for.
SPEC = SystemSpec.polynesia(backend="pallas", n_shards=1,
                            placement="stacked", delta_store=False)


@pytest.fixture(scope="module")
def workload():
    rng = np.random.default_rng(13)
    sch = schema.make_schema("t", 8, 32)
    table = schema.gen_table(rng, sch, ROWS)
    # ~1500 writes a round: a capacity ship inside execute, then a flush
    stream = schema.gen_update_stream(rng, sch, ROWS, 9000, write_ratio=0.5)
    queries = (engine.gen_queries(rng, 6, 8)
               + engine.gen_queries(rng, 3, 8, same_column=True))
    return table, split_stream(stream, N_ROUNDS), split_queries(queries,
                                                                N_ROUNDS)


def drive(workload, record: bool, rounds=range(N_ROUNDS), session=None,
          spec=SPEC):
    """Run rounds of the workload through a pallas session (eager,
    `SPEC`, unless ``spec`` says otherwise: its scans run the kernel entry
    points); return it with its answers."""
    table, chunks, qchunks = workload
    if session is None:
        session = HTAPSession(spec, table)
        if record:
            session.cost.record_spans()
    answers = []
    for r in rounds:
        if r:
            session.advance_round()
        session.execute(chunks[r])
        answers += session.query_batch(qchunks[r])
    return session, answers


@pytest.fixture(scope="module")
def recorded(workload):
    return drive(workload, record=True)


def children(spans, i):
    return [s for s in spans if s.parent == i]


def test_ship_batches_hold_drain_ship_stages_and_applies(recorded):
    spans = recorded[0].cost.spans
    batches = [i for i, s in enumerate(spans) if s.name == "ship_batch"]
    assert len(batches) >= N_ROUNDS + 2   # capacity ships and flushes
    for i in batches:
        kids = children(spans, i)
        names = collections.Counter(s.name for s in kids)
        assert names["drain"] == names["ship"] == names["stages"] == 1
        applies = [s for s in kids if s.name == "apply"]
        assert len(applies) == names["apply"] > 1
        # one apply per column, each its own timeline node of this batch
        node = spans[i].node
        assert {s.node for s in applies} == {
            f"{node}:c{c}" for c in range(8)
            if f"{node}:c{c}" in recorded[0].cost.tags}
        assert all(s.node == node for s in kids if s.name != "apply")
        assert spans[i].n == sum(int(t.meta.get("n_applied", 0))
                                 for t in recorded[0].cost.tags.values()
                                 if t.node.startswith(node + ":c"))


def test_each_apply_holds_one_reencode_and_one_swap(recorded):
    spans = recorded[0].cost.spans
    applies = [i for i, s in enumerate(spans) if s.name == "apply"]
    assert applies
    for i in applies:
        names = sorted(s.name for s in children(spans, i))
        assert names == ["reencode", "swap"]
        [reencode] = [s for s in children(spans, i) if s.name == "reencode"]
        assert reencode.n == ROWS


def test_each_query_group_holds_its_scans(recorded):
    session, answers = recorded
    spans = session.cost.spans
    groups = [i for i, s in enumerate(spans) if s.name == "ana"]
    assert len(groups) == session.counters()["query_groups"] >= 2
    assert sum(spans[i].n for i in groups) == len(answers)
    for i in groups:
        kids = children(spans, i)
        assert kids and {s.name for s in kids} == {"scan"}
    # a group's snapshot is pinned just before it, outside it
    snaps = [s for s in spans if s.name == "snapshot"]
    assert len(snaps) == len(groups)


def test_spans_nest_by_parent_and_time(recorded):
    spans = recorded[0].cost.spans
    top = collections.Counter(s.name for s in spans if s.parent < 0)
    assert set(top) == {"txn", "ship_batch", "snapshot", "ana"}
    for i, s in enumerate(spans):
        assert s.t0 <= s.t1 and not math.isnan(s.t1)
        if s.parent >= 0:
            p = spans[s.parent]
            assert s.parent < i and p.t0 <= s.t0 and s.t1 <= p.t1
            assert s.node == p.node or p.name == "ship_batch"
    # the row-store work of execute holds no ship: ships follow it
    assert not any(s.name != "txn" and s.parent >= 0
                   and spans[s.parent].name == "txn" for s in spans)


def test_counters_count_the_spans(recorded):
    session, _ = recorded
    spans = session.cost.spans
    c = session.counters()
    names = collections.Counter(s.name for s in spans)
    assert c["ships"] == names["ship_batch"]
    assert c["query_groups"] == names["ana"]
    assert c["applications"] == names["apply"]
    assert c["kernel_traces"] == sum(kernel_trace_counts().values())
    assert c["compactions"] == c["delta_appends"] == 0
    # reading the counters does not close the session
    session.query_batch([])
    assert session.counters() == c


def test_recording_changes_no_answer_and_no_stat(workload, recorded):
    session, answers = recorded
    plain, plain_answers = drive(workload, record=False)
    assert plain.cost.spans == []
    assert answers == plain_answers
    counts = {k: v for k, v in plain.counters().items()
              if k != "kernel_traces"}
    assert counts == {k: v for k, v in session.counters().items()
                      if k != "kernel_traces"}
    a, b = drive(workload, True)[0].finish(), plain.finish()
    assert a.results == b.results and a.stats == b.stats
    assert a.txn_seconds == b.txn_seconds and a.ana_seconds == b.ana_seconds


def test_nothing_recorded_or_annotated_while_off(workload, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("a trace annotation was built")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    session, answers = drive(workload, record=False)
    assert session.cost.spans == [] and answers
    # the same session annotates once recording is on
    session.cost.record_spans()
    with pytest.raises(AssertionError, match="annotation"):
        session.execute(workload[1][0])


def test_checkpoint_of_a_recording_session_is_unchanged(workload, tmp_path):
    """Spans never enter a checkpoint: a recording session's checkpoint
    equals a plain one's, and its restore continues to the same answers."""
    steps = {}
    for record in (True, False):
        session, _ = drive(workload, record, rounds=range(2))
        steps[record] = session.checkpoint(str(tmp_path / str(record)))
    on, off = (load_arrays(str(tmp_path / str(r)), steps[r])
               for r in (True, False))
    assert sorted(on) == sorted(off)
    for k in on:
        np.testing.assert_array_equal(on[k], off[k])
    restored = HTAPSession.restore(str(tmp_path / "True"))
    assert restored.cost.spans == [] and not restored.cost.recording
    _, rest = drive(workload, False, rounds=[2], session=restored)
    _, whole = drive(workload, False)
    assert rest == whole[-len(rest):]


@pytest.mark.parametrize("plane", [
    dict(n_shards=4, placement="stacked", delta_store=False),
    dict(n_shards=1, placement="stacked", delta_store=True),
], ids=["sharded_eager", "delta"])
def test_other_planes_record_the_same_structure(workload, plane):
    """Four stacked islands re-encode through `apply_updates_shards`; the
    delta plane appends to overlays with no dictionary stages. Both
    record ship batches, applies and scanning query groups as the eager
    plane does, and answer as an unrecorded session of the same plane."""
    spec = SPEC.replace(**plane)
    session, answers = drive(workload, True, spec=spec)
    plain, plain_answers = drive(workload, False, spec=spec)
    assert answers == plain_answers and plain.cost.spans == []
    spans = session.cost.spans
    names = collections.Counter(s.name for s in spans)
    c = session.counters()
    assert c["ships"] == names["ship_batch"] >= N_ROUNDS + 2
    assert c["query_groups"] == names["ana"] >= 2
    for i, s in enumerate(spans):
        kids = collections.Counter(k.name for k in children(spans, i))
        if s.name == "ship_batch":
            assert kids["drain"] == kids["ship"] == 1 and kids["apply"] > 1
            assert kids["stages"] == (0 if plane["delta_store"] else 1)
        elif s.name == "apply" and not plane["delta_store"]:
            assert kids == {"reencode": 1, "swap": 1}
        elif s.name == "ana":
            assert set(kids) == {"scan"}


def test_scan_spans_wrap_kernel_entry_points():
    from repro.core import backend

    assert set(backend.SCAN_ENTRY_POINTS) <= set(
        backend.KERNEL_ENTRY_POINTS)
    for name in backend.SCAN_ENTRY_POINTS:
        assert getattr(backend, name).__wrapped__.__name__ == name
