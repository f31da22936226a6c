"""Elastic island lifecycle (core/elastic.py): online resharding,
checkpoint/restore, crash-recovery replay, and the closed-session guards.

The load-bearing properties:

* a mid-session `resize_islands` schedule is answer-neutral — the
  partition is not observable in query results — for every backend and
  update plane, and the golden-pinned answers survive a 1 -> 4 -> 2 trip;
* `checkpoint` + `restore` continue a session bit-identically (answers
  AND modeled seconds), including restoring onto a *different* shard
  count/backend (answers only — the modeled plane legitimately differs);
* an injected crash (`SessionCrash`) recovered via `run_with_recovery`
  replays to the crash-free run's exact answers.
"""

import numpy as np
import pytest

from repro.checkpoint import latest_step
from repro.core import elastic, engine, schema
from repro.core.dsm import ShardedView
from repro.core.session import (HTAPSession, SessionClosedError, SystemSpec,
                                resolve_spec)
from repro.core.workload import split_queries, split_stream

N_ROUNDS = 4


@pytest.fixture(scope="module")
def tiny_workload():
    rng = np.random.default_rng(0)
    sch = schema.make_schema("t", 3, 32)
    table = schema.gen_table(rng, sch, 600)
    stream = schema.gen_update_stream(rng, sch, 600, 1500, write_ratio=0.5)
    queries = engine.gen_queries(rng, 6, 3)
    return table, stream, queries


def _rounds(stream, queries, n_rounds=N_ROUNDS):
    return (split_stream(stream, n_rounds),
            split_queries(list(queries), n_rounds))


def _drive(session, chunks, qchunks, resize=None, start=0):
    """Round loop with an optional {round: islands-or-(islands, placement)}
    resize schedule applied after each round's query batch."""
    for r in range(start, len(chunks)):
        if r > start:
            session.advance_round()
        session.execute(chunks[r])
        session.query_batch(qchunks[r])
        if resize and r in resize:
            tgt = resize[r]
            n, pl = tgt if isinstance(tgt, tuple) else (tgt, None)
            session.resize_islands(n, placement=pl)
    return session.finish()


# ---------------------------------------------------------------------------
# online resharding
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("delta", [False, True])
def test_resize_roundtrip_bit_identical(tiny_workload, backend, delta):
    """1 -> 4 -> 2 mid-session: answers match the static single-island
    run bit for bit on both backends and both update planes."""
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend=backend, n_shards=1,
                                timing="timeline", delta_store=delta)
    base = _drive(HTAPSession(spec, table), chunks, qchunks)
    res = _drive(HTAPSession(spec, table), chunks, qchunks,
                 resize={0: 4, 1: 2})
    assert [int(a) for a in res.results] == [int(a) for a in base.results]
    trail = res.stats["resizes"]
    assert [(r["from"], r["to"]) for r in trail] == [(1, 4), (4, 2)]
    assert all(r["node"].endswith(f"reshard{i}")
               for i, r in enumerate(trail))
    assert "resizes" not in base.stats


def test_resize_is_priced_on_the_accel_lane(tiny_workload):
    """The reshard node lands on the fixed-function lane with nonzero
    duration, and queries (not transactions) wait on it."""
    from repro.core.hwmodel import HardwareModel
    from repro.core.timeline import simulate_timeline
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend="numpy", timing="timeline")
    session = HTAPSession(spec, table)
    for r in range(2):
        if r:
            session.advance_round()
        session.execute(chunks[r])
        session.query_batch(qchunks[r])
    node = session.resize_islands(4)
    assert node == "r1:reshard0"
    # queries wait: every column's visibility node is now the reshard
    assert set(session._vis_node.values()) == {node}
    # transactions don't: background rebalance never joins the stall set
    assert node not in session._round_prop
    tl = simulate_timeline(session.cost, HardwareModel(session.hw))
    sched = {n.tag.node: n for n in tl.nodes}
    assert sched[node].lane == "accel" and sched[node].seconds > 0
    session.finish()


def test_resize_placement_transitions_single_device(tiny_workload):
    """stacked -> mesh -> stacked on one device: answers unchanged, the
    island mesh context installs on entry and releases on exit, and the
    repartitioned shards are re-placed device-resident at the swap."""
    from repro.distributed import current_island_mesh
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend="pallas", n_shards=1,
                                timing="timeline")
    base = _drive(HTAPSession(spec, table), chunks, qchunks)
    prev = current_island_mesh()
    session = HTAPSession(spec, table)
    session.execute(chunks[0])
    session.query_batch(qchunks[0])
    session.resize_islands(1, placement="mesh")
    assert session.be.placement == "mesh"
    assert current_island_mesh() is session.be.mesh
    # the repartitioned shards are device-resident at the swap: the
    # eager plane's columns are resident views, the delta plane's host
    # columns go through the Phase-2 residency handoff
    assert all(isinstance(col, ShardedView) or c in session.cons._resident
               for c, col in session.replica.columns.items())
    session.advance_round()
    session.execute(chunks[1])
    session.query_batch(qchunks[1])
    session.resize_islands(1, placement="stacked")
    assert current_island_mesh() is prev
    for r in range(2, N_ROUNDS):
        session.advance_round()
        session.execute(chunks[r])
        session.query_batch(qchunks[r])
    res = session.finish()
    assert current_island_mesh() is prev
    assert [int(a) for a in res.results] == [int(a) for a in base.results]


def test_resize_matches_golden_answers(small_workload):
    """The golden-pinned Polynesia answers survive a 1 -> 4 -> 2 resize
    trip on the standard seed workload (same pin as test_golden_answers,
    elastic edition — a resize-path answer drift fails here)."""
    import json
    import pathlib
    table, stream, queries = small_workload
    golden = json.load(open(pathlib.Path(__file__).parent
                            / "golden_answers.json"))["results"]["Polynesia"]
    chunks, qchunks = _rounds(stream, queries, n_rounds=8)
    spec = resolve_spec("Polynesia", n_shards=1, timing="timeline")
    res = _drive(HTAPSession(spec, table), chunks, qchunks,
                 resize={1: 4, 4: 2})
    assert [int(a) for a in res.results] == golden


def test_resize_guards(tiny_workload):
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    session = HTAPSession(SystemSpec.polynesia(backend="numpy"), table)
    session.execute(chunks[0])
    with pytest.raises(ValueError, match="n_islands"):
        session.resize_islands(0)
    # same count + placement: explicit no-op, no reshard node emitted
    assert session.resize_islands(1) is None
    assert session.resizes == []
    session.finish()
    with pytest.raises(SessionClosedError):
        session.resize_islands(2)
    # non-MI kinds have no analytical islands to repartition
    si = HTAPSession(resolve_spec("SI-SS", backend="numpy"), table)
    with pytest.raises(ValueError, match="multi"):
        si.resize_islands(2)
    si.finish()
    # ad-hoc backend instances cannot be re-wrapped by registry name
    from repro.core.backend import NumpyBackend
    adhoc = HTAPSession(SystemSpec.polynesia(backend=NumpyBackend()), table)
    with pytest.raises(ValueError, match="registered"):
        adhoc.resize_islands(2)
    adhoc.finish()


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------

def test_checkpoint_restore_continues_bit_identically(tiny_workload,
                                                      tmp_path):
    """Same-spec restore: answers AND modeled seconds match the
    uninterrupted session exactly."""
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend="numpy", timing="timeline",
                                async_propagation=True)
    ref = HTAPSession(spec, table)
    cut = HTAPSession(spec, table)
    for r in range(2):
        for s in (ref, cut):
            if r:
                s.advance_round()
            s.execute(chunks[r])
            s.query_batch(qchunks[r])
    step = cut.checkpoint(str(tmp_path))
    assert latest_step(str(tmp_path)) == step
    restored = HTAPSession.restore(str(tmp_path))
    a = _drive(ref, chunks, qchunks, start=2)
    b = _drive(restored, chunks, qchunks, start=2)
    assert [int(x) for x in b.results] == [int(x) for x in a.results]
    assert b.txn_seconds == a.txn_seconds
    assert b.ana_seconds == a.ana_seconds
    assert b.stats["timeline"] == a.stats["timeline"]
    assert b.stats["latency"] == a.stats["latency"]
    # the interrupted original keeps running too (checkpoint is a pure read)
    cut.finish()


@pytest.mark.parametrize("target", ["pallas", "numpy@4", "pallas@2"])
def test_restore_onto_different_target(tiny_workload, tmp_path, target):
    """Elastic restart: a checkpoint taken on numpy@1 restores onto a
    different backend / shard count and replays to the same answers."""
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend="numpy", n_shards=1,
                                timing="timeline")
    ref = HTAPSession(spec, table)
    cut = HTAPSession(spec, table)
    for s in (ref, cut):
        s.execute(chunks[0])
        s.query_batch(qchunks[0])
    cut.checkpoint(str(tmp_path), step=1)
    restored = HTAPSession.restore(
        str(tmp_path), spec=SystemSpec.polynesia(backend=target,
                                                 timing="timeline"))
    a = _drive(ref, chunks, qchunks, start=1)
    b = _drive(restored, chunks, qchunks, start=1)
    assert [int(x) for x in b.results] == [int(x) for x in a.results]
    cut.finish()


def test_checkpoint_preserves_pending_backlog(tiny_workload, tmp_path):
    """The executed-but-unshipped update backlog survives the round trip:
    checkpoint right after execute (before any query flushes), restore,
    and the restored session's queries see every executed update."""
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend="numpy", timing="timeline")
    s = HTAPSession(spec, table)
    s.execute(chunks[0])
    assert s.store.pending_updates > 0
    s.checkpoint(str(tmp_path), step=0)
    restored = HTAPSession.restore(str(tmp_path))
    assert restored.store.pending_updates == s.store.pending_updates
    a = s.query_batch(qchunks[0])
    b = restored.query_batch(qchunks[0])
    assert [int(x) for x in b] == [int(x) for x in a]
    s.finish()
    restored.finish()


def test_delta_checkpoint_refuses_eager_target(tiny_workload, tmp_path):
    table, stream, queries = tiny_workload
    chunks, qchunks = _rounds(stream, queries)
    spec = SystemSpec.polynesia(backend="numpy", timing="timeline",
                                delta_store=True)
    s = HTAPSession(spec, table)
    s.execute(chunks[0])
    s.query_batch(qchunks[0])
    assert sum(d.n_overlay for d in s._deltas.values()) > 0
    s.checkpoint(str(tmp_path))
    with pytest.raises(ValueError, match="delta-overlay"):
        HTAPSession.restore(
            str(tmp_path),
            spec=SystemSpec.polynesia(backend="numpy", timing="timeline",
                                      delta_store=False))
    # the delta-plane target works and continues bit-identically
    restored = HTAPSession.restore(str(tmp_path))
    a = _drive(s, chunks, qchunks, start=1)
    b = _drive(restored, chunks, qchunks, start=1)
    assert [int(x) for x in b.results] == [int(x) for x in a.results]


def test_restore_requires_committed_checkpoint(tmp_path):
    with pytest.raises(FileNotFoundError):
        HTAPSession.restore(str(tmp_path))


# ---------------------------------------------------------------------------
# crash-recovery replay
# ---------------------------------------------------------------------------

def test_crash_recovery_replays_to_same_answers(tiny_workload, tmp_path):
    table, stream, queries = tiny_workload
    spec = SystemSpec.polynesia(backend="numpy", timing="timeline")
    chunks, qchunks = _rounds(stream, queries)
    base = _drive(HTAPSession(spec, table), chunks, qchunks)
    res, recovered = elastic.run_with_recovery(
        spec, table, stream, queries, N_ROUNDS, str(tmp_path),
        crash_after_ships=2)
    assert recovered
    assert [int(x) for x in res.results] == [int(x) for x in base.results]


def test_crash_before_first_commit_cold_restarts(tiny_workload, tmp_path):
    """crash_after_ships=0 dies before anything is checkpointed: recovery
    degenerates to a clean cold restart from round 0."""
    table, stream, queries = tiny_workload
    spec = SystemSpec.polynesia(backend="numpy", timing="timeline")
    chunks, qchunks = _rounds(stream, queries)
    base = _drive(HTAPSession(spec, table), chunks, qchunks)
    res, recovered = elastic.run_with_recovery(
        spec, table, stream, queries, N_ROUNDS, str(tmp_path),
        crash_after_ships=0)
    assert recovered
    assert latest_step(str(tmp_path)) is None
    assert [int(x) for x in res.results] == [int(x) for x in base.results]


def test_crash_recovery_onto_resized_target(tiny_workload, tmp_path):
    """The elastic restart: crash on 1 island, recover onto 4."""
    table, stream, queries = tiny_workload
    spec = SystemSpec.polynesia(backend="numpy", n_shards=1,
                                timing="timeline")
    chunks, qchunks = _rounds(stream, queries)
    base = _drive(HTAPSession(spec, table), chunks, qchunks)
    res, recovered = elastic.run_with_recovery(
        spec, table, stream, queries, N_ROUNDS, str(tmp_path),
        crash_after_ships=3,
        restore_spec=SystemSpec.polynesia(backend="numpy", n_shards=4,
                                          timing="timeline"))
    assert recovered
    assert [int(x) for x in res.results] == [int(x) for x in base.results]


def test_crash_env_hook(tiny_workload, monkeypatch):
    table, stream, queries = tiny_workload
    monkeypatch.setenv("REPRO_CRASH_AFTER", "0")
    session = HTAPSession(SystemSpec.polynesia(backend="numpy"), table)
    assert session.crash_after_ships == 0
    with pytest.raises(elastic.SessionCrash):
        session.execute(stream)
        session.query_batch(list(queries))
    session.abort()
    with pytest.raises(SessionClosedError):
        session.query_batch(list(queries))
    session.abort()  # idempotent
    monkeypatch.setenv("REPRO_CRASH_AFTER", "nope")
    with pytest.raises(ValueError, match="REPRO_CRASH_AFTER"):
        HTAPSession(SystemSpec.polynesia(backend="numpy"), table)


def test_abort_releases_mesh_context(tiny_workload):
    from repro.distributed import current_island_mesh
    table, _, _ = tiny_workload
    prev = current_island_mesh()
    session = HTAPSession(SystemSpec.polynesia(backend="pallas@1/mesh"),
                          table)
    assert current_island_mesh() is session.be.mesh
    session.abort()
    assert current_island_mesh() is prev


# ---------------------------------------------------------------------------
# closed-session error matrix
# ---------------------------------------------------------------------------

def test_session_closed_error_matrix(tiny_workload, tmp_path):
    """Every post-close surface raises SessionClosedError (a RuntimeError
    subclass, so pre-existing `except RuntimeError` guards still work)."""
    table, stream, queries = tiny_workload
    session = HTAPSession(SystemSpec.polynesia(backend="numpy"), table)
    session.execute(stream)
    session.finish()
    assert issubclass(SessionClosedError, RuntimeError)
    for call in [lambda: session.execute(stream),
                 lambda: session.query(queries[0]),
                 lambda: session.query_batch(list(queries)),
                 lambda: session.advance_round(),
                 lambda: session.flush_updates(),
                 lambda: session.finish(),
                 lambda: session.checkpoint(str(tmp_path)),
                 lambda: session.resize_islands(2)]:
        with pytest.raises(SessionClosedError, match="finished"):
            call()
    # abort after finish is a no-op, not an error
    session.abort()
