"""Execution-backend layer (core/backend.py): registry semantics,
cross-backend bit-identical equivalence for all six systems, kernel
dispatch verification, and per-operator wrapper-vs-reference checks."""

import numpy as np
import pytest

from repro.core import backend as backend_mod
from repro.core import engine, htap
from repro.core.application import apply_updates, apply_updates_naive
from repro.core.backend import (NumpyBackend, PallasBackend,
                                default_backend_name, get_backend,
                                set_default_backend)
from repro.core.consistency import ConsistencyManager
from repro.core.dsm import DSMReplica, decode_column, encode_column
from repro.core.nsm import make_entries
from repro.core.shipping import ship_updates

from repro.core.backend import KERNEL_ENTRY_POINTS


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_get_backend_resolution():
    assert get_backend() is get_backend(default_backend_name())
    assert isinstance(get_backend("numpy"), NumpyBackend)
    assert isinstance(get_backend("pallas"), PallasBackend)
    be = NumpyBackend()
    assert get_backend(be) is be
    with pytest.raises(KeyError, match="unknown backend"):
        get_backend("cuda")


def test_set_default_backend_roundtrip():
    old = default_backend_name()
    try:
        set_default_backend("pallas")
        assert isinstance(get_backend(None), PallasBackend)
    finally:
        set_default_backend(old)
    with pytest.raises(KeyError):
        set_default_backend("not-a-backend")


# ---------------------------------------------------------------------------
# cross-backend equivalence: all six systems, bit-identical answers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def workload(small_workload):
    return small_workload


@pytest.fixture(scope="module")
def runs(workload):
    table, stream, queries = workload
    return {name: {be: htap.run(name, table, stream, queries,
                                n_rounds=4, backend=be)
                   for be in ("numpy", "pallas")}
            for name in htap.PRESETS}


@pytest.mark.parametrize("system", list(htap.PRESETS))
def test_cross_backend_identical_answers(runs, system):
    a, b = runs[system]["numpy"], runs[system]["pallas"]
    assert a.results == b.results
    # jit trace profiles legitimately differ per backend; everything else
    # in stats must be bit-identical
    assert ({k: v for k, v in a.stats.items() if k != "traces"}
            == {k: v for k, v in b.stats.items() if k != "traces"})
    assert (a.n_txn, a.n_ana) == (b.n_txn, b.n_ana)


def test_numpy_backend_matches_default(workload):
    """backend=None must answer exactly like the numpy reference, whatever
    the session default resolves to (the CI matrix sets REPRO_BACKEND to
    sharded/mesh specs); island-count-dependent stats only have to match
    when the default is the plain unsharded tier."""
    table, stream, queries = workload
    a = htap.run("Polynesia", table, stream, queries, n_rounds=4)
    b = htap.run("Polynesia", table, stream, queries, n_rounds=4,
                 backend="numpy", n_shards=1)
    assert a.results == b.results
    be = get_backend(None)
    if getattr(be, "n_shards", 1) == 1 and be.placement == "stacked":
        assert a.stats == b.stats


# ---------------------------------------------------------------------------
# kernel dispatch: the PallasBackend must actually run the kernels
# ---------------------------------------------------------------------------

def _count_kernel_calls(monkeypatch):
    counts = {}

    def wrap(name, real):
        def inner(*args, **kwargs):
            counts[name] = counts.get(name, 0) + 1
            return real(*args, **kwargs)
        return inner

    for name in KERNEL_ENTRY_POINTS:
        monkeypatch.setattr(backend_mod, name,
                            wrap(name, getattr(backend_mod, name)))
    return counts


def test_pallas_backend_invokes_kernels(workload, monkeypatch):
    counts = _count_kernel_calls(monkeypatch)
    table, stream, queries = workload
    # pinned to the eager update plane: the apply-pipeline counts asserted
    # below come from the two-stage apply, which delta_store bypasses
    htap.run("Polynesia", table, stream, queries, n_rounds=4,
             backend="pallas", delta_store=False)
    scans = counts.get("scan_filter_agg", 0) + counts.get(
        "scan_filter_agg_batch", 0)
    assert scans > 0, counts                       # fused analytical scans
    # the fused apply pipeline (sort + merge networks in one launch)
    # replaces the separate sorter/probe dispatches of the old ship path
    assert counts.get("apply_pipeline_batch", 0) > 0, counts
    assert counts.get("merge_sorted_runs", 0) > 0, counts   # merge unit
    assert counts.get("snapshot_copy", 0) > 0, counts       # copy unit
    # no per-batch hash-table builds or probes remain: staged writes are
    # encoded by the binary-search staged encoder, inside no launch at all
    assert counts.get("probe", 0) == 0, counts
    assert counts.get("build_table", 0) == 0, counts
    sorts = counts.get("sort_1024", 0) + counts.get("sort_rows", 0)
    assert sorts == 0, counts                      # fused into the pipeline


def test_pallas_backend_fuses_query_groups(workload, monkeypatch):
    """Same-column-set queries must share one multi-query kernel launch."""
    counts = _count_kernel_calls(monkeypatch)
    table, _, _ = workload
    rng = np.random.default_rng(3)
    queries = engine.gen_queries(rng, 8, 4, join_fraction=0.0,
                                 same_column=True)   # one column set
    replica = DSMReplica.from_table(table)
    view = replica.columns
    got = engine.run_query_group_dsm(view, queries, backend="pallas")
    exp = [engine.run_query_dsm(view, q, backend="numpy") for q in queries]
    assert got == exp
    assert counts.get("scan_filter_agg_batch", 0) == 1
    assert counts.get("scan_filter_agg", 0) == 0


def test_pallas_backend_uses_kernel_for_join_queries(workload, monkeypatch):
    """A join-query group rides ONE fused scan+join device call — not the
    old per-query mask scan + host bincount glue."""
    counts = _count_kernel_calls(monkeypatch)
    table, _, _ = workload
    rng = np.random.default_rng(7)
    queries = engine.gen_queries(rng, 4, 4, join_fraction=1.0,
                                 same_column=True)   # one column set
    replica = DSMReplica.from_table(table)
    for group in engine.group_queries(queries):
        got = engine.run_query_group_dsm(replica.columns, group,
                                         backend="pallas")
        exp = [engine.run_query_dsm(replica.columns, q, backend="numpy")
               for q in group]
        assert got == exp
    n_groups = len(engine.group_queries(queries))
    assert counts.get("scan_filter_agg_join", 0) == n_groups, counts
    assert counts.get("scan_filter_agg", 0) == 0, counts
    assert counts.get("probe", 0) == 0, counts


def test_numpy_backend_never_touches_kernels(workload, monkeypatch):
    counts = _count_kernel_calls(monkeypatch)
    table, stream, queries = workload
    htap.run_polynesia(table, stream, queries, n_rounds=4, backend="numpy")
    assert counts == {}


# ---------------------------------------------------------------------------
# per-operator wrapper-vs-reference checks (deterministic property sweeps)
# ---------------------------------------------------------------------------

def _encoded(rng, n, k, invalid_frac=0.1):
    col = encode_column(rng.choice(np.arange(0, 1 << 24, dtype=np.int32),
                                   size=k, replace=False)[
                            rng.integers(0, k, size=n)])
    if invalid_frac:
        import jax.numpy as jnp
        valid = rng.random(n) >= invalid_frac
        col = type(col)(codes=col.codes, dictionary=col.dictionary,
                        valid=jnp.asarray(valid), version=col.version)
    return col


@pytest.mark.parametrize("n,k", [(4096, 31), (5000, 997)])
def test_filter_agg_operators_match(rng, n, k):
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    fcol = _encoded(rng, n, k)
    acol = _encoded(rng, n, min(k, 257))
    d = np.asarray(fcol.dictionary)
    bounds = [(int(d[k // 4]), int(d[3 * k // 4])), (0, 1 << 24), (5, 4)]
    for lo, hi in bounds:
        assert pl_be.filter_agg(fcol, acol, lo, hi) == \
            np_be.filter_agg(fcol, acol, lo, hi)
        np.testing.assert_array_equal(pl_be.filter_mask(fcol, lo, hi),
                                      np_be.filter_mask(fcol, lo, hi))
    assert pl_be.filter_agg_batch(fcol, acol, bounds) == \
        np_be.filter_agg_batch(fcol, acol, bounds)


def test_hash_join_operator_matches(rng):
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    left = _encoded(rng, 3000, 101)
    right = _encoded(rng, 2000, 211)
    mask = rng.random(3000) < 0.4
    assert pl_be.hash_join_count(left, right) == \
        np_be.hash_join_count(left, right)
    assert pl_be.hash_join_count(left, right, left_mask=mask) == \
        np_be.hash_join_count(left, right, left_mask=mask)
    assert pl_be.hash_join_count(left, left, left_mask=mask) == \
        np_be.hash_join_count(left, left, left_mask=mask)


def test_merge_update_logs_matches(rng):
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    ids = np.arange(700, dtype=np.int64)
    rng.shuffle(ids)
    logs = []
    for t in range(4):
        mine = np.sort(ids[t::4])
        logs.append(make_entries(mine, np.ones(len(mine), np.int8),
                                 rng.integers(0, 1000, len(mine)).astype(np.int32),
                                 rng.integers(0, 50, len(mine)).astype(np.int64),
                                 rng.integers(0, 4, len(mine)).astype(np.int32)))
    a = np_be.merge_update_logs(logs)
    b = pl_be.merge_update_logs(logs)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a["commit_id"], np.arange(700))


def test_merge_update_logs_int64_commit_ids(rng, monkeypatch):
    """Commit ids beyond 2^31 merge on the kernel path — the old int32
    numpy fallback is gone (the comparator tree now runs on (hi, lo)
    int32 lanes of the full int64 key)."""
    counts = _count_kernel_calls(monkeypatch)
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    base = np.int64(2) ** 31  # first id already overflows int32
    ids = base + rng.choice(np.int64(10) ** 9, 600, replace=False)
    ids[:60] -= base  # mix in small ids so both words exercise the compare
    rng.shuffle(ids)
    logs = []
    for t in range(4):
        mine = np.sort(ids[t::4])
        logs.append(make_entries(mine, np.ones(len(mine), np.int8),
                                 rng.integers(0, 1000, len(mine)).astype(np.int32),
                                 rng.integers(0, 50, len(mine)).astype(np.int64),
                                 rng.integers(0, 4, len(mine)).astype(np.int32)))
    a = np_be.merge_update_logs(logs)
    b = pl_be.merge_update_logs(logs)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(b["commit_id"], np.sort(ids))
    assert counts.get("merge_sorted_runs", 0) > 0, counts  # no fallback


def test_sort_merge_encode_operators_match(rng):
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    vals = rng.integers(0, 1 << 20, size=700).astype(np.int32)
    np.testing.assert_array_equal(pl_be.sort_unique(vals),
                                  np_be.sort_unique(vals))
    old_d = np.unique(rng.integers(0, 1 << 20, size=300).astype(np.int32))
    upd_d = np.unique(rng.integers(0, 1 << 20, size=90).astype(np.int32))
    merged_np = np_be.merge_dictionaries(old_d, upd_d)
    merged_pl = pl_be.merge_dictionaries(old_d, upd_d)
    np.testing.assert_array_equal(merged_np, merged_pl)
    # encoder: exact on values present in the dictionary
    sample = merged_np[rng.integers(0, len(merged_np), size=256)]
    np.testing.assert_array_equal(pl_be.make_encoder(merged_np)(sample),
                                  np_be.make_encoder(merged_np)(sample))


def test_apply_stages_batch_fused_matches_reference(rng):
    """The single-launch fused ship-batch pipeline (sort network + bitonic
    merge + staged encode) must reproduce the compositional reference
    stage-for-stage, including the rows it routes to the fallback (empty
    sides, int64-range values, sentinel collisions)."""
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    per_column = []
    for _ in range(6):
        o = np.unique(rng.integers(0, 1 << 20,
                                   rng.integers(1, 800))).astype(np.int64)
        wv = rng.integers(0, 1 << 20, rng.integers(1, 260)).astype(np.int64)
        per_column.append((o, wv))
    # fallback rows: empty sides and a value beyond int32
    per_column.append((np.unique(rng.integers(0, 100, 20)).astype(np.int64),
                       np.empty(0, np.int64)))
    per_column.append((np.empty(0, np.int64),
                       rng.integers(0, 100, 13).astype(np.int64)))
    per_column.append((np.asarray([3, 9], np.int64),
                       np.asarray([1 << 40, 5], np.int64)))
    fused = pl_be.apply_stages_batch(per_column)
    ref = np_be.apply_stages_batch(per_column)
    for i, ((u_f, d_f, enc_f, m_f), (u_r, d_r, enc_r, m_r)) in enumerate(
            zip(fused, ref)):
        np.testing.assert_array_equal(u_f, u_r, err_msg=f"col {i} update")
        np.testing.assert_array_equal(d_f, d_r, err_msg=f"col {i} merged")
        np.testing.assert_array_equal(m_f, m_r, err_msg=f"col {i} remap")
        probe_vals = per_column[i][1][:5]
        np.testing.assert_array_equal(enc_f(probe_vals), enc_r(probe_vals),
                                      err_msg=f"col {i} encode")


def test_snapshot_column_operator(rng):
    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    col = _encoded(rng, 20_000, 63, invalid_frac=0.0)
    for be in (np_be, pl_be):
        snap = be.snapshot_column(col)
        np.testing.assert_array_equal(np.asarray(snap.codes),
                                      np.asarray(col.codes))
        assert snap.version == col.version
    # carrying clean chunks from a previous snapshot must still equal src
    prev = pl_be.snapshot_column(col)
    snap = pl_be.snapshot_column(col, prev=prev)
    np.testing.assert_array_equal(np.asarray(snap.codes),
                                  np.asarray(col.codes))


def test_ship_updates_equivalent_buffers(rng):
    stream_len = 600
    logs = []
    ids = np.arange(stream_len, dtype=np.int64)
    rng.shuffle(ids)
    for t in range(4):
        mine = np.sort(ids[t::4])
        logs.append(make_entries(mine, np.ones(len(mine), np.int8),
                                 rng.integers(0, 1000, len(mine)).astype(np.int32),
                                 rng.integers(0, 50, len(mine)).astype(np.int64),
                                 rng.integers(0, 6, len(mine)).astype(np.int32)))
    a = ship_updates([l.copy() for l in logs], 6, backend="numpy")
    b = ship_updates([l.copy() for l in logs], 6, backend="pallas")
    assert set(a) == set(b)
    for c in a:
        np.testing.assert_array_equal(a[c], b[c])


def test_apply_updates_backends_agree_and_match_naive(rng):
    """Deterministic stand-in for the hypothesis oracle test (test_update_
    application.py skips when hypothesis is unavailable)."""
    base = rng.integers(0, 500, size=300).astype(np.int32)
    col = encode_column(base)
    m = 64
    ups = make_entries(np.arange(m, dtype=np.int64),
                       np.ones(m, dtype=np.int8),
                       rng.integers(0, 500, m).astype(np.int32),
                       rng.integers(0, 300, m).astype(np.int64),
                       np.zeros(m, dtype=np.int32))
    oracle = apply_updates_naive(col, ups)
    got = {be: apply_updates(col, ups, backend=be)
           for be in ("numpy", "pallas")}
    for be, g in got.items():
        # decoded contents must match the naive oracle (the dictionary may
        # be a superset: the optimized path keeps overwritten update values)
        np.testing.assert_array_equal(np.asarray(decode_column(g)),
                                      np.asarray(decode_column(oracle)), be)
    np.testing.assert_array_equal(np.asarray(got["numpy"].dictionary),
                                  np.asarray(got["pallas"].dictionary))
    np.testing.assert_array_equal(np.asarray(got["numpy"].codes),
                                  np.asarray(got["pallas"].codes))


def test_consistency_manager_pallas_snapshots(rng):
    table = rng.integers(0, 50, size=(9000, 3)).astype(np.int32)
    rep = DSMReplica.from_table(table)
    cons = ConsistencyManager(rep, backend="pallas")
    h = cons.begin_query([0, 1])
    before = np.asarray(decode_column(cons.read(h, 0))).copy()
    ups = make_entries(np.array([0], np.int64), np.array([1], np.int8),
                       np.array([999_999], np.int32), np.array([5], np.int64),
                       np.array([0], np.int32))
    cons.on_update(0, apply_updates(rep.columns[0], ups, backend="pallas"))
    # pinned snapshot is frozen; a fresh query sees the update
    np.testing.assert_array_equal(
        np.asarray(decode_column(cons.read(h, 0))), before)
    cons.end_query(h)
    h2 = cons.begin_query([0])
    assert int(np.asarray(decode_column(cons.read(h2, 0)))[5]) == 999_999
    cons.end_query(h2)


@pytest.mark.parametrize("sizes", [(5000,), (5000, 300, 9000), (300, 4097)],
                         ids=["one_large", "mixed", "small_and_large"])
def test_apply_stages_batch_large_dictionaries_merge_by_insertion(rng,
                                                                  sizes):
    """Old dictionaries past the merge networks' reach merge by inserting
    the new values at their thresholds, stage-for-stage equal to the
    reference, whichever other columns share the batch."""
    from repro.core.backend import MERGE_NETWORK_MAX_DICT

    np_be, pl_be = get_backend("numpy"), get_backend("pallas")
    per_column = []
    for k in sizes:
        o = np.sort(rng.choice(1 << 22, k, replace=False)).astype(np.int32)
        wv = np.concatenate([rng.integers(0, 1 << 22, 100),
                             rng.choice(o, 20)]).astype(np.int32)
        per_column.append((o, wv))
    assert any(k > MERGE_NETWORK_MAX_DICT for k in sizes)
    got = pl_be.apply_stages_batch(per_column)
    ref = np_be.apply_stages_batch(per_column)
    for i, ((u_g, d_g, enc_g, m_g), (u_r, d_r, _, m_r)) in enumerate(
            zip(got, ref)):
        np.testing.assert_array_equal(u_g, u_r, err_msg=f"col {i} update")
        np.testing.assert_array_equal(d_g, d_r, err_msg=f"col {i} merged")
        np.testing.assert_array_equal(m_g, m_r, err_msg=f"col {i} remap")
        np.testing.assert_array_equal(enc_g(per_column[i][1]),
                                      np.searchsorted(d_r, per_column[i][1]))


def test_snapshot_column_resident_tracks_dirty_chunks_on_device(rng):
    """A device-resident column's snapshot computes its tracking buffer on
    the device when the dictionaries match, and copies every chunk when
    they differ; either way the snapshot equals the column."""
    import jax

    pl_be = get_backend("pallas")
    host = _encoded(rng, 40_000, 63, invalid_frac=0.0)
    col = type(host)(codes=jax.device_put(host.codes),
                     dictionary=host.dictionary,
                     valid=jax.device_put(np.asarray(host.valid)))
    prev = pl_be.snapshot_column(col)
    changed = np.asarray(host.codes).copy()
    changed[[5, 20_000]] = (changed[[5, 20_000]] + 1) % 63
    nxt = type(col)(codes=jax.device_put(changed), dictionary=col.dictionary,
                    valid=col.valid, version=1)
    with backend_mod.counting_kernel_calls() as counts:
        for before in (prev, None):
            snap = pl_be.snapshot_column(nxt, prev=before)
            np.testing.assert_array_equal(np.asarray(snap.codes), changed)
            assert snap.version == 1
    assert counts == {"dirty_chunks": 1, "snapshot_copy": 2}
