"""Four analytical islands, each holding its shard on its own device.

On the eager plane of a ``pallas@4/mesh`` session every replica column is a
resident `ShardedView` from set-up on: stage 3 runs on each island's
device (`application.apply_updates_islands`), snapshots copy each shard
on its device, and no column is sharded through the host again. Every
test runs with four devices (the ``host_devices`` mark of ``conftest.py``)
at 2^12 rows, and holds the islands to the plain reference of the
benchmark (`chipbench.reference.Reference`) and to ``numpy@1``.
"""

import json

import numpy as np
import pytest

from chipbench import gen, run
from chipbench.reference import Reference
from repro.core import engine
from repro.core.application import apply_updates, apply_updates_where
from repro.core.backend import get_backend
from repro.core.dsm import ShardedView, encode_column, host_column
from repro.core.nsm import make_entries
from repro.core.schema import UpdateStream
from repro.core.session import HTAPSession, SystemSpec

ROWS = 1 << 12
COLS = 8
DOMAIN = 1 << 24
SEED = 2**31 + 5
MESH = SystemSpec.polynesia(backend="pallas@4/mesh", delta_store=False,
                            timing="phase")
NUMPY = SystemSpec.polynesia(backend="numpy", n_shards=1,
                             placement="stacked", delta_store=False,
                             timing="phase")

pytestmark = pytest.mark.host_devices


def to_txns(t: gen.Txns) -> UpdateStream:
    return UpdateStream(t.thread_id, t.commit_id, t.op, t.row, t.col,
                        t.value)


def to_queries(qs):
    return [engine.Query(i, q.filter_col, q.lo, q.hi, q.agg_col, q.join_col)
            for i, q in enumerate(qs)]


@pytest.fixture(scope="module")
def data():
    """The benchmark's table and traffic for one seed at 2^12 rows."""
    table = gen.gen_table(SEED, ROWS, COLS, 32, DOMAIN)
    queries = gen.gen_queries(gen.rng_for(SEED, 2), 40, COLS, DOMAIN, 0.3,
                              0.5)
    return table, queries


def serve(spec, table, chunks, query_sets):
    """A session of ``spec`` executing each chunk, flushing, then
    answering its query set; the session and each batch's answers."""
    session = HTAPSession(spec, table.copy())
    answers = []
    for chunk, qs in zip(chunks, query_sets):
        session.execute(to_txns(chunk))
        session.flush_updates()
        answers.append(session.query_batch(to_queries(qs)))
    return session, answers


def assert_resident(session, islands=4):
    for col in session.replica.columns.values():
        assert isinstance(col, ShardedView) and col.n_shards == islands
        assert len(col.codes.sharding.device_set) == islands


def test_writes_match_the_reference_and_numpy_after_each_flush(data):
    table, queries = data
    stream = gen.TxnStream(SEED, ROWS, COLS, DOMAIN, 0.5, 4)
    chunks = [stream.take(1500) for _ in range(6)]
    sets = [queries[4 * i:4 * i + 4] for i in range(6)]
    session, got = serve(MESH, table, chunks, sets)
    _, want_numpy = serve(NUMPY, table, chunks, sets)
    ref, want = Reference(table, DOMAIN), []
    for chunk, qs in zip(chunks, sets):
        ref.apply(chunk)
        want.append([ref.answer(q) for q in qs])
    assert got == want == want_numpy
    c = session.counters()
    # every applied column re-encoded on each of its four islands, and
    # no column was sharded through the host after set-up
    assert c["reencodes_device"] == 4 * c["applications"] > 0
    assert c["reencodes_host"] == c["views_built"] == 0
    assert c["views_resident"] > 0
    assert_resident(session)


def test_deletes_match_numpy_after_each_flush(data):
    table, queries = data
    stream = gen.TxnStream(SEED + 1, ROWS, COLS, DOMAIN, 0.5, 4)
    chunks = [stream.take(1500) for _ in range(4)]
    for chunk in chunks:          # every fifth write deletes its row
        writes = np.flatnonzero(chunk.op == gen.OP_MODIFY)
        chunk.op[writes[::5]] = 3
    sets = [queries[4 * i:4 * i + 4] for i in range(4)]
    session, got = serve(MESH, table, chunks, sets)
    _, want = serve(NUMPY, table, chunks, sets)
    assert got == want
    c = session.counters()
    assert c["reencodes_device"] == 4 * c["applications"] > 0
    assert c["reencodes_host"] == c["views_built"] == 0


def batch(rows, values, ops):
    n = len(rows)
    return make_entries(np.arange(n, dtype=np.int64),
                        np.array(ops, dtype=np.int8),
                        np.array(values, dtype=np.int32),
                        np.array(rows, dtype=np.int64),
                        np.zeros(n, dtype=np.int32))


def test_an_insert_batch_takes_the_host_path_and_stays_resident():
    """An insert batch changes the column's length: its stage 3 runs on
    the host and its shards go back to their devices. The next batch,
    modifies and deletes only, runs on the devices again. Both give the
    column ``numpy@1`` gives, and the same answers."""
    rng = np.random.default_rng(4)
    be = get_backend("pallas@4/mesh")
    host = encode_column(rng.integers(0, 50, ROWS).astype(np.int32))
    view = be.shard_view(host)
    n = ROWS
    first = batch([3, n, n + 1, 17, 2000], [7, 9, 10_000, 51, 3],
                  [1, 2, 2, 3, 1])
    second = batch([0, n + 1, 4000, 1025, 5], [60, 61, 62, 1, 9],
                   [1, 1, 3, 1, 3])
    for step, where in ((first, "host"), (second, "device")):
        view, ran = apply_updates_where(view, step, backend=be)
        host = apply_updates(host, step, backend="numpy")
        assert ran == where
        assert isinstance(view, ShardedView)
        assert len(view.codes.sharding.device_set) == 4
        got = host_column(view)
        for field in ("codes", "dictionary", "valid"):
            np.testing.assert_array_equal(getattr(got, field),
                                          getattr(host, field))
        assert got.version == host.version
        q = [engine.Query(0, 0, 5, 60, 0, None),
             engine.Query(1, 0, 0, 10_000, 0, 0)]
        assert (engine.run_query_group_dsm({0: view}, q[:1], backend=be)
                + engine.run_query_group_dsm({0: view}, q[1:], backend=be)
                == [engine.run_query_dsm({0: host}, x, backend="numpy")
                    for x in q])


def test_queries_only_match_the_reference(data):
    """With no writes nothing is applied: every batch reads the shards
    placed at set-up, snapshotted on their devices."""
    table, queries = data
    stream = gen.TxnStream(SEED, ROWS, COLS, DOMAIN, 0.0, 4)
    prefill = stream.take(1 << 14)
    sets = [queries[4 * i:4 * i + 4] for i in range(5)]
    session = HTAPSession(MESH, table.copy())
    session.execute(to_txns(prefill))
    ref = Reference(table, DOMAIN)
    for qs in sets:
        assert session.query_batch(to_queries(qs)) == [ref.answer(q)
                                                       for q in qs]
    c = session.counters()
    assert c["applications"] == c["views_built"] == 0
    assert c["views_resident"] > 0 and c["views_shared"] > 0
    assert_resident(session)


def test_a_pinned_snapshot_survives_an_apply_on_the_islands(data):
    """A snapshot pinned before a flush still reads its own version on
    every island afterwards: the apply writes new device arrays."""
    table, _ = data
    stream = gen.TxnStream(SEED, ROWS, COLS, DOMAIN, 1.0, 4)
    session = HTAPSession(MESH, table.copy())
    session.execute(to_txns(stream.take(400)))
    session.flush_updates()
    handle = session.cons.begin_query([2])
    pinned = session.cons.read_scan(handle, 2)
    before = host_column(pinned)
    session.execute(to_txns(stream.take(400)))
    session.flush_updates()
    assert session.replica.columns[2].version > pinned.version
    again = host_column(session.cons.read_scan(handle, 2))
    for field in ("codes", "dictionary", "valid"):
        np.testing.assert_array_equal(getattr(again, field),
                                      getattr(before, field))
    session.cons.end_query(handle)


def test_checkpoint_then_resize_from_four_islands_to_two(
        data, tmp_path):
    """A checkpoint of the resident islands, then a resize to two, give
    the answers ``numpy@1`` gives; so does a restore of the checkpoint
    onto four islands."""
    table, queries = data
    stream = gen.TxnStream(SEED + 2, ROWS, COLS, DOMAIN, 0.5, 4)
    chunks = [stream.take(1500) for _ in range(4)]
    sets = [queries[4 * i:4 * i + 4] for i in range(4)]
    _, want = serve(NUMPY, table, chunks, sets)
    session, got = serve(MESH, table, chunks[:2], sets[:2])
    step = session.checkpoint(str(tmp_path))
    session.resize_islands(2)
    assert_resident(session, islands=2)
    restored = HTAPSession.restore(str(tmp_path), step=step)
    assert_resident(restored)
    for s in (session, restored):
        rest = []
        for chunk, qs in zip(chunks[2:], sets[2:]):
            s.execute(to_txns(chunk))
            s.flush_updates()
            rest.append(s.query_batch(to_queries(qs)))
        assert got + rest == want
        assert s.counters()["reencodes_host"] == 0
    session.finish()
    restored.finish()


def test_the_mesh4_ana_cell_runs_correct(process_state, capsys):
    """The benchmark's four-island cell, at 2^12 rows on the mesh itself
    (not the numpy islands of the benchmark's own tests)."""
    assert run.main(["--workload", "mesh4.ana", "--seed", str(2**31 + 9),
                     "--seconds", "2", "--trace", "0"], require_chip=False,
                    overrides={"n_rows": ROWS}) == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["check"]["checked_answers"]["value"] > 0
