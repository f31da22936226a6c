"""The scans' dictionary decode by path: the rule, the histogram's exact
answers against the gather and numpy, and the decode counter.

Past `DICT_SELECT_MAX` padded entries a flat scan counts its aggregate
(and join) codes per query on the MXU and takes one int64 dot with the
dictionary on the host; these cases run that Pallas branch in interpret
mode.
"""

import jax
import numpy as np
import pytest

from repro.core import engine, schema
from repro.core.session import HTAPSession, SystemSpec
from repro.core.workload import split_stream
from repro.kernels import common
from repro.kernels.dict_ops import dict_ops, ops
from repro.kernels.dict_ops.ref import scan_filter_agg_batch_ref
from repro.kernels.hash_probe import ops as hash_ops

I32 = np.iinfo(np.int32)
N_ROWS = 5003        # no multiple of any block


@pytest.fixture
def interpret_mode():
    common.set_interpret_override("1")
    yield
    common.set_interpret_override(None)


def _columns(k: int, seed: int = 0):
    """Filter, aggregate and join codes below ``k`` (the aggregate codes
    holding both sides of a high-part edge and the last live code), a
    validity with holes, and a dictionary of int32 extremes, 0 and
    negative values."""
    rng = np.random.default_rng(seed + k)
    fc, ac, jc = (rng.integers(0, k, N_ROWS).astype(np.int32)
                  for _ in range(3))
    edges = [c for c in (dict_ops.HIST_LO - 1, dict_ops.HIST_LO, k - 1)
             if c < k]
    ac[:3 * len(edges)] = np.repeat(edges, 3)
    fv, jv = rng.random(N_ROWS) < 0.9, rng.random(N_ROWS) < 0.8
    d = rng.integers(I32.min, I32.max, k, dtype=np.int64).astype(np.int32)
    d[:4] = [I32.min, I32.max, 0, -1]
    d[-1] = I32.min
    return fc, ac, jc, fv, jv, d


def _bounds(k: int, nq: int):
    """``nq`` code ranges over a ``k``-entry dictionary; past one query,
    one covers every code and one is empty (past four, in each block of
    the histogram's queries)."""
    four = [(0, k), (3, max(4, k // 3)), (k - 1, k), (9, 3)]
    return {1: [(1, k - 1)],
            2: [(0, k), (k // 2, k // 2)],
            4: four,
            8: four + [(5, 5), (k // 2, k), (0, 1), (0, k)]}[nq]


def _gathered(fc, ac, fv, d, bounds):
    """The decoding scan of the same columns (XLA's take), exact."""
    dpad, barr = ops.pad_dictionary_pow2(d), ops.pad_bounds_pow2(bounds)
    parts = jax.jit(dict_ops.scan_exact_decoded, static_argnums=(5, 6))(
        fc, ac, fv, dpad, barr, 4096, True)
    sums, counts = ops.assemble_exact(*parts, axis=0)
    return [(int(s), int(c)) for s, c in zip(sums, counts)][:len(bounds)]


@pytest.mark.parametrize("nq", [1, 2, 4, 8])
@pytest.mark.parametrize("k", [65, 300, 1 << 12])
def test_histogram_answers_as_the_gather_and_numpy(interpret_mode, k, nq):
    fc, ac, _, fv, _, d = _columns(k)
    bounds = _bounds(k, nq)
    assert ops.pad_dictionary_pow2(d).shape[0] == ops.DICT_PAD_MIN
    before = common.decode_counts()["hist"]
    got = ops.scan_filter_agg_batch(fc, ac, fv, d, bounds)
    assert common.decode_counts()["hist"] == before + 1
    want = scan_filter_agg_batch_ref(fc, ac, fv, d, bounds)
    assert got == want == _gathered(fc, ac, fv, d, bounds)


@pytest.mark.parametrize("nq", [1, 2, 4])
@pytest.mark.parametrize("k", [65, 300, 1 << 12])
def test_histogram_join_counts_as_a_numpy_build_side(interpret_mode, k, nq):
    fc, ac, jc, fv, jv, d = _columns(k, seed=1)
    rcount = np.bincount(jc[jv], minlength=k).astype(np.int32)
    bounds = _bounds(k, nq)
    got = hash_ops.scan_filter_agg_join(fc, ac, jc, fv, jv, d, rcount,
                                        bounds)
    for (s, c, j), (lo, hi), want in zip(
            got, bounds, scan_filter_agg_batch_ref(fc, ac, fv, d, bounds)):
        m = (fc >= lo) & (fc < hi) & fv & jv
        assert (s, c) == want
        assert j == int(rcount[jc[m]].astype(np.int64).sum())


@pytest.mark.parametrize("live,nq", [(300, 4), (40_000, 4), (300, 8)])
def test_histogram_counts_each_live_code(interpret_mode, live, nq):
    """The kernel's counts are numpy's bincount of each query's rows,
    across the 2^15-code chunk edge too; nothing is counted in a chunk
    wholly past the live length, nor for an empty range; eight queries
    run as two blocks of the grid."""
    rng = np.random.default_rng(live)
    f = rng.integers(0, 1000, N_ROWS).astype(np.int32)
    c = rng.integers(0, live, N_ROWS).astype(np.int32)
    chunk = dict_ops.HIST_HI_CHUNK * dict_ops.HIST_LO
    c[:4] = [x for x in (chunk - 1, chunk, live - 1, 0)]
    c = np.minimum(c, live - 1)
    v = rng.random(N_ROWS) < 0.9
    bounds = np.asarray([(0, 1000), (10, 500), (7, 7), (9, 3),
                         (300, 301), (0, 0), (999, 1000), (0, 640)][:nq],
                        np.int32)
    out = jax.jit(dict_ops._hist_counts, static_argnums=(5, 6))(
        bounds, f, c, v, ops.live_length(np.zeros(live)), ops.DICT_PAD_MIN,
        True)
    got = np.asarray(out).reshape(len(bounds), -1)
    assert got.shape[1] == ops.DICT_PAD_MIN
    for q, (lo, hi) in enumerate(bounds):
        m = (f >= lo) & (f < hi) & v
        np.testing.assert_array_equal(
            got[q], np.bincount(c[m], minlength=ops.DICT_PAD_MIN))


def test_decode_rule():
    smax = dict_ops.DICT_SELECT_MAX
    assert dict_ops.decode_path(smax) == "select"
    assert dict_ops.decode_path(smax, hist=False) == "select"
    assert dict_ops.decode_path(2 * smax) == "hist"
    assert dict_ops.decode_path(ops.DICT_PAD_MIN) == "hist"
    assert dict_ops.decode_path(ops.DICT_PAD_MIN, hist=False) == "gather"


@pytest.mark.parametrize("width,nq,path", [
    (32, 1, "select"), (32, 8, "select"), (1 << 17, 1, "hist"),
    (1 << 17, 8, "hist")])
def test_scan_program_branches_on_its_shapes(interpret_mode, width, nq,
                                             path):
    """`scan_filter_agg_exact_kernel` returns a 1-tuple of (Q, hi, lo)
    counts on the histogram branch, else the (n_blocks, Q) partials."""
    n = 3 * 4096
    args = (np.zeros(n, np.int32), np.zeros(n, np.int32),
            np.ones(n, bool), np.zeros(width, np.int32),
            np.zeros((nq, 2), np.int32), np.full(1, width, np.int32))
    out = dict_ops.scan_filter_agg_exact_kernel(*args, block=4096)
    if path == "hist":
        assert len(out) == 1
        assert out[0].shape == (nq, width // dict_ops.HIST_LO,
                                dict_ops.HIST_LO)
    else:
        assert [p.shape for p in out] == [(3, nq)] * 4


@pytest.mark.parametrize("mode", ["1", "auto"], ids=["interpret", "lowered"])
def test_decode_counter_counts_each_column_by_path(mode):
    """One decode per aggregate column and per join build side: small
    dictionaries select, wider ones count codes for any number of queries
    (the lowered CPU path gathers instead)."""
    common.set_interpret_override(mode)
    try:
        fc, ac, jc, fv, jv, d = _columns(300)
        small = d[:32]
        common.reset_decode_counts()
        ops.scan_filter_agg_batch(fc % 32, ac % 32, fv, small, [(0, 9)])
        ops.scan_filter_agg_batch(fc, ac, fv, d, [(0, 9)])
        ops.scan_filter_agg_batch(fc, ac, fv, d, [(0, 9)] * 8)
        rcount = np.bincount(jc[jv], minlength=300).astype(np.int32)
        hash_ops.scan_filter_agg_join(fc % 32, ac % 32, jc, fv, jv, small,
                                      rcount, [(0, 9)])
        wide = 3 if mode == "1" else 0
        assert common.decode_counts() == {"select": 2, "hist": wide,
                                          "gather": 3 - wide}
    finally:
        common.set_interpret_override(None)


def test_eager_session_past_64_entries_counts_codes(interpret_mode):
    """An eager pallas session whose dictionaries grow past 64 entries
    answers as numpy does, its scans counting codes."""
    rng = np.random.default_rng(3)
    sch = schema.make_schema("t", 3, 32)
    table = schema.gen_table(rng, sch, 2000)
    stream = schema.gen_update_stream(rng, sch, 2000, 1200, write_ratio=0.5)
    queries = engine.gen_queries(rng, 6, 3, join_fraction=0.5)
    answers = {}
    for backend in ("pallas", "numpy"):
        spec = SystemSpec.polynesia(backend=backend, n_shards=1,
                                    delta_store=False)
        session = HTAPSession(spec, table)
        out = []
        for r, chunk in enumerate(split_stream(stream, 2)):
            if r:
                session.advance_round()
            session.execute(chunk)
            out.append(session.query_batch(queries))
        answers[backend] = out
        if backend == "pallas":
            sizes = [c.dict_size for c in session.replica.columns.values()]
            assert min(sizes) > dict_ops.DICT_SELECT_MAX
            counts = session.counters()
            assert counts["scan_decodes_hist"] > 0
            assert counts["scan_decodes_gather"] == 0
    assert answers["pallas"] == answers["numpy"]
