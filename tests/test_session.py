"""Session API (core/session.py): presets, incremental equivalence, guards.

The load-bearing property: an `HTAPSession` answers by *visibility point*,
not by batch shape — any sub-chunking of the txn stream between two query
batches produces bit-identical answers and the same total modeled cost as
the batch wrapper, for every preset, backend and island count. The
hypothesis sweep explores random chunkings on the numpy reference; the
deterministic sweep pins one adversarial chunking (uneven cuts + an empty
sub-chunk) across preset x {numpy, pallas} x shards {1, 4}.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import engine, htap, schema
from repro.core.session import (ALL_PRESETS, HTAPSession, SystemSpec,
                                resolve_spec)
from repro.core.workload import (mixed_traffic_schedule, slice_stream,
                                 split_queries, split_stream)

N_ROUNDS = 3


@pytest.fixture(scope="module")
def tiny_workload():
    rng = np.random.default_rng(0)
    sch = schema.make_schema("t", 3, 32)
    table = schema.gen_table(rng, sch, 600)
    stream = schema.gen_update_stream(rng, sch, 600, 1500, write_ratio=0.5)
    queries = engine.gen_queries(rng, 6, 3)
    return table, stream, queries


def _sub_chunks(chunk, cuts):
    """Split one round's chunk at the given (unsorted, unclamped) cuts."""
    bounds = sorted({min(max(int(c), 0), len(chunk)) for c in cuts}
                    | {0, len(chunk)})
    return [slice_stream(chunk, lo, hi)
            for lo, hi in zip(bounds, bounds[1:])] or [chunk]


def _drive(name, table, stream, queries, cuts_per_round=None, **spec_kw):
    """Drive a session like the batch wrapper, optionally sub-chunking
    each round's txn chunk at the given cut positions. Returns the session
    (finished) and its RunResult."""
    spec = resolve_spec(name, **spec_kw)
    session = HTAPSession(spec, table)
    if spec.kind == "ideal_txn":
        for sub in _sub_chunks(stream, (cuts_per_round or [[]])[0]):
            session.execute(sub)
        return session, session.finish()
    if spec.kind == "ana_only":
        for q in queries:
            session.query(q)
        return session, session.finish()
    for r, (txn_chunk, q_chunk) in enumerate(
            zip(split_stream(stream, N_ROUNDS),
                split_queries(queries, N_ROUNDS))):
        if r:
            session.advance_round()
        cuts = cuts_per_round[r] if cuts_per_round else []
        for sub in _sub_chunks(txn_chunk, cuts):
            session.execute(sub)
        session.query_batch(q_chunk)
    return session, session.finish()


def _assert_equivalent(ref_session, ref_res, chunk_session, chunk_res):
    assert chunk_res.results == ref_res.results
    assert (chunk_res.n_txn, chunk_res.n_ana) == (ref_res.n_txn,
                                                  ref_res.n_ana)
    ref_tot = ref_session.cost.totals()
    chunk_tot = chunk_session.cost.totals()
    assert set(ref_tot) == set(chunk_tot)
    for key, v in ref_tot.items():
        # identical up to float summation order (sub-chunks emit the same
        # per-entry costs in more events)
        assert chunk_tot[key] == pytest.approx(v, rel=1e-9, abs=1e-9), key


# ---------------------------------------------------------------------------
# wrapper equivalence: the batch drivers ARE one session chunking
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ALL_PRESETS))
def test_batch_wrapper_is_session_round_chunking(tiny_workload, name):
    table, stream, queries = tiny_workload
    wrapper = htap.run(name, table, stream, queries, n_rounds=N_ROUNDS,
                       backend="numpy", n_shards=1)
    _, res = _drive(name, table, stream, queries, backend="numpy",
                    n_shards=1)
    assert res.results == wrapper.results
    assert res.stats == wrapper.stats
    assert (res.txn_seconds, res.ana_seconds, res.energy_joules) == \
        (wrapper.txn_seconds, wrapper.ana_seconds, wrapper.energy_joules)


# ---------------------------------------------------------------------------
# deterministic adversarial chunking: preset x backend x shards
# ---------------------------------------------------------------------------

# uneven cuts incl. a duplicate (-> an empty sub-chunk) in every round
ADVERSARIAL_CUTS = [[7, 7, 450], [1], [499, 200]]


@pytest.mark.parametrize("backend,n_shards", [("numpy", 1), ("numpy", 4),
                                              ("pallas", 1), ("pallas", 4)])
@pytest.mark.parametrize("name", sorted(ALL_PRESETS))
def test_chunking_invariance_all_presets_backends_shards(
        tiny_workload, name, backend, n_shards):
    table, stream, queries = tiny_workload
    ref = _drive(name, table, stream, queries, backend=backend,
                 n_shards=n_shards)
    chunked = _drive(name, table, stream, queries,
                     cuts_per_round=ADVERSARIAL_CUTS, backend=backend,
                     n_shards=n_shards)
    _assert_equivalent(*ref, *chunked)


# ---------------------------------------------------------------------------
# hypothesis: arbitrary chunkings on the numpy reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(ALL_PRESETS))
def test_property_arbitrary_chunking_equivalent(tiny_workload, name):
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (pip install .[test])")
    from hypothesis import given, settings, strategies as st

    table, stream, queries = tiny_workload
    ref = _drive(name, table, stream, queries, backend="numpy", n_shards=1)

    @settings(max_examples=8, deadline=None)
    @given(cuts=st.lists(st.lists(st.integers(0, 500), min_size=0,
                                  max_size=3),
                         min_size=N_ROUNDS, max_size=N_ROUNDS))
    def prop(cuts):
        chunked = _drive(name, table, stream, queries, cuts_per_round=cuts,
                         backend="numpy", n_shards=1)
        _assert_equivalent(*ref, *chunked)

    prop()


# ---------------------------------------------------------------------------
# open-system semantics: mid-round queries see exactly their prefix
# ---------------------------------------------------------------------------

def test_mid_round_query_sees_committed_prefix(tiny_workload):
    """A query issued after K commits answers exactly like a batch run
    whose round boundary is at K — the visibility point is the API."""
    table, stream, queries = tiny_workload
    q = queries[0]
    for k in (0, 137, 750, len(stream)):
        session = HTAPSession(SystemSpec.polynesia(backend="numpy",
                                                   n_shards=1), table)
        session.execute(slice_stream(stream, 0, k))
        mid = session.query(q)
        # oracle: one-round batch run over only the first k transactions
        oracle = htap.run("Polynesia", table, slice_stream(stream, 0, k),
                          [q], n_rounds=1, backend="numpy", n_shards=1)
        assert [mid] == oracle.results, f"visibility point {k}"


def test_mvcc_fresh_round_query_sees_prior_commits(tiny_workload):
    """A SI-MVCC query in a round that has not executed yet snapshots at
    'now' — everything committed in earlier rounds is visible (regression:
    the timestamp used to fall back to 0, answering over the initial
    table)."""
    table, stream, queries = tiny_workload
    q = queries[0]
    session = HTAPSession(SystemSpec.si_mvcc(), table)
    session.execute(stream)
    session.advance_round()
    fresh = session.query(q)
    # oracle: the row store after the whole stream (end-of-stream MVCC
    # read == MI end-of-round visibility)
    oracle = htap.run("MI+SW", table, stream, [q], n_rounds=1,
                      backend="numpy", n_shards=1)
    assert [fresh] == oracle.results
    initial = htap.run("Ana-Only", table, queries=[q]).results
    assert [fresh] != initial, "query ignored every committed transaction"


def test_ana_only_queries_across_rounds(tiny_workload):
    """Ana-Only sessions accept advance_round like any other kind; query
    node names stay unique across rounds (regression: duplicate timeline
    node 'q0:ana')."""
    table, _, queries = tiny_workload
    session = HTAPSession(SystemSpec.ana_only(), table)
    a = session.query(queries[0])
    session.advance_round()
    b = session.query(queries[0])
    assert a == b                       # the initial table never changes
    res = session.finish()
    assert res.n_ana == 2 and res.results == [a, b]


def test_mixed_traffic_deterministic_and_batch_inexpressible(tiny_workload):
    table, stream, queries = tiny_workload
    clients = [queries[:3], queries[3:]]
    arrivals = mixed_traffic_schedule(np.random.default_rng(5), clients,
                                      n_txn=len(stream), txn_rate=1e6,
                                      query_rates=[4e3, 6e3])
    assert arrivals
    spec = SystemSpec.polynesia(backend="numpy", n_shards=1)
    a = htap.run_mixed_traffic(spec, table, stream, arrivals)
    b = htap.run_mixed_traffic(spec, table, stream, arrivals)
    assert a.results == b.results and a.n_txn == len(stream)
    # the schedule genuinely interleaves: queries land at more than one
    # distinct visibility point inside the stream, including positions no
    # practical uniform split (2..16 rounds) would put a boundary at
    positions = {arr.position for arr in arrivals}
    uniform = {int(bound) for n in range(2, 17)
               for bound in np.linspace(0, len(stream), n + 1)}
    assert len(positions) > 1
    assert positions - uniform, (positions, "all on uniform boundaries")


# ---------------------------------------------------------------------------
# spec + session guard rails
# ---------------------------------------------------------------------------

def test_spec_presets_are_frozen_and_named():
    spec = SystemSpec.polynesia()
    assert spec.name == "Polynesia" and spec.kind == "multi_instance"
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.name = "nope"  # type: ignore[misc]


def test_spec_replace_and_resolve():
    spec = SystemSpec.mi_sw(backend="numpy").replace(n_shards=4)
    assert spec.n_shards == 4 and spec.backend == "numpy"
    assert resolve_spec("MI+SW", n_shards=4).n_shards == 4
    assert resolve_spec(spec) is spec
    with pytest.raises(KeyError, match="preset"):
        resolve_spec("Not-A-System")
    with pytest.raises(ValueError, match="kind"):
        SystemSpec(name="x", kind="bogus")


def test_session_rejects_wrong_surface(tiny_workload):
    table, stream, queries = tiny_workload
    ana = HTAPSession(SystemSpec.ana_only(), table)
    with pytest.raises(ValueError, match="transactional"):
        ana.execute(stream)
    ideal = HTAPSession(SystemSpec.ideal_txn(), table)
    with pytest.raises(ValueError, match="analytical"):
        ideal.query(queries[0])
    si = HTAPSession(SystemSpec.si_ss(), table)
    with pytest.raises(ValueError, match="multiple-instance"):
        si.flush_updates()


def test_session_finish_closes(tiny_workload):
    table, stream, queries = tiny_workload
    session = HTAPSession(SystemSpec.polynesia(), table)
    session.execute(split_stream(stream, N_ROUNDS)[0])
    session.query(queries[0])
    res = session.finish()
    assert res.n_txn and res.n_ana == 1
    for call in (lambda: session.execute(stream),
                 lambda: session.query(queries[0]),
                 lambda: session.advance_round(),
                 lambda: session.finish()):
        with pytest.raises(RuntimeError, match="finished"):
            call()


def test_async_requires_timeline_at_session_construction(tiny_workload):
    table, _, _ = tiny_workload
    with pytest.raises(ValueError, match="timeline"):
        HTAPSession(SystemSpec.polynesia(async_propagation=True,
                                         timing="phase"), table)


def test_empty_query_batch_is_noop(tiny_workload):
    """An empty batch must not flush pending updates (no-queries rounds
    carry their backlog forward, exactly like the batch drivers)."""
    table, stream, _ = tiny_workload
    session = HTAPSession(SystemSpec.polynesia(backend="numpy"), table)
    session.execute(split_stream(stream, N_ROUNDS)[0])
    pending = session.store.pending_updates
    assert session.query_batch([]) == []
    assert session.store.pending_updates == pending


def test_sequential_mesh_sessions_release_island_mesh(tiny_workload):
    """A mesh session installs its island mesh as the process-global
    context at construction; finish() must put back whatever was there
    before, so a second session — or an ad-hoc get_backend("...@N/mesh")
    with a different island count — never resolves against the first
    session's stale mesh. Regression: finish() used to leave the mesh
    installed."""
    from repro.distributed import current_island_mesh
    table, stream, queries = tiny_workload
    prev = current_island_mesh()

    s1, r1 = _drive("Polynesia", table, stream, queries,
                    backend="pallas@1/mesh")
    assert current_island_mesh() is prev  # released by finish()

    s2, r2 = _drive("Polynesia", table, stream, queries,
                    backend="pallas@1/mesh")
    assert current_island_mesh() is prev
    assert r1.results == r2.results
    assert r1.stats["placement"] == r2.stats["placement"] == "mesh"


def test_mesh_session_installs_mesh_for_its_lifetime(tiny_workload):
    """While the session is live, its mesh IS the process-global context
    (ad-hoc backend resolution inside the session sees it); finish()
    restores the previous context even when one was already installed."""
    from repro.distributed import current_island_mesh
    table, _, _ = tiny_workload
    outer = HTAPSession(SystemSpec.polynesia(backend="pallas@1/mesh"), table)
    assert current_island_mesh() is outer.be.mesh
    inner = HTAPSession(SystemSpec.polynesia(backend="pallas@1/mesh"), table)
    assert current_island_mesh() is inner.be.mesh
    inner.finish()
    assert current_island_mesh() is outer.be.mesh  # restored, not cleared
    outer.finish()
    assert current_island_mesh() is None


# ---------------------------------------------------------------------------
# Device-resident replica: the eager plane on one accelerator replica
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ship_workload():
    """Enough writes per round for capacity ships inside `execute` as well
    as the flush before each query batch; half the queries self-join."""
    rng = np.random.default_rng(5)
    sch = schema.make_schema("t", 4, 32)
    table = schema.gen_table(rng, sch, 3000)
    stream = schema.gen_update_stream(rng, sch, 3000, 12_000,
                                      write_ratio=0.5)
    queries = engine.gen_queries(rng, 8, 4, join_fraction=0.5)
    return table, stream, queries


def _serve(spec, workload, rounds=4):
    table, stream, queries = workload
    session = HTAPSession(spec, table)
    answers = []
    for r, chunk in enumerate(split_stream(stream, rounds)):
        if r:
            session.advance_round()
        for sub in _sub_chunks(chunk, [len(chunk) // 3]):
            session.execute(sub)
        answers.append(session.query_batch(queries))
    return session, answers


def test_resident_eager_session_matches_numpy(ship_workload):
    """The eager plane on a single-replica pallas backend keeps its
    replica on the device and re-encodes every applied column there; its
    answers equal the numpy reference's across capacity ships and
    flushes."""
    import jax

    spec = SystemSpec.polynesia(backend="pallas", n_shards=1,
                                delta_store=False)
    session, answers = _serve(spec, ship_workload)
    _, want = _serve(spec.replace(backend="numpy"), ship_workload)
    assert answers == want
    counts = session.counters()
    assert counts["ships"] > 4                      # capacity ships too
    assert counts["reencodes_device"] == counts["applications"] > 0
    assert counts["reencodes_host"] == 0
    for c, col in session.replica.columns.items():
        assert isinstance(col.codes, jax.Array)
        assert isinstance(col.valid, jax.Array)
        # the joins' build side followed every swap
        codes, valid = np.asarray(col.codes), np.asarray(col.valid)
        np.testing.assert_array_equal(
            session.cons.build_sides[c].counts,
            np.bincount(codes[valid], minlength=col.dict_size))


@pytest.mark.parametrize("plane", [
    dict(backend="pallas", n_shards=1, delta_store=True),
    dict(backend="pallas", n_shards=2, delta_store=False)],
    ids=["delta", "two_shards"])
def test_host_planes_never_reencode_on_device(ship_workload, plane):
    """The delta plane and the sharded islands keep their host base: no
    column re-encodes on the device."""
    session, _ = _serve(SystemSpec.polynesia(**plane), ship_workload,
                        rounds=2)
    counts = session.counters()
    assert counts["reencodes_device"] == 0
    # every column that did re-encode (a sharded apply, a compaction) did
    # so on the host, counted per island shard
    assert counts["reencodes_host"] == (
        counts["applications"] * plane["n_shards"] if plane["n_shards"] > 1
        else counts["compactions"])
    assert session.cons.build_sides == {}
    if plane["delta_store"]:
        for col in session.replica.columns.values():
            assert isinstance(col.codes, np.ndarray)


def test_pinned_snapshot_survives_device_apply(ship_workload):
    """A snapshot pinned before an apply on the device still reads its own
    version afterwards (the apply writes new device arrays), and its join
    build side stays that version's."""
    table, stream, queries = ship_workload
    session = HTAPSession(SystemSpec.polynesia(
        backend="pallas", n_shards=1, delta_store=False), table)
    first, second = split_stream(stream, 2)
    session.execute(first)
    session.flush_updates()
    c = int(np.bincount(second.col[second.op == 1]).argmax())
    handle = session.cons.begin_query([c])
    pinned = session.cons.read(handle, c)
    codes, valid = np.array(pinned.codes), np.array(pinned.valid)
    version = pinned.version
    counts = session.cons.build_counts(handle, c).copy()
    np.testing.assert_array_equal(
        counts, np.bincount(codes[valid], minlength=pinned.dict_size))
    session.execute(second)
    session.flush_updates()
    assert session.replica.columns[c].version > version
    again = session.cons.read(handle, c)
    assert again.version == version
    np.testing.assert_array_equal(np.asarray(again.codes), codes)
    np.testing.assert_array_equal(np.asarray(again.valid), valid)
    np.testing.assert_array_equal(session.cons.build_counts(handle, c),
                                  counts)
    session.cons.end_query(handle)
