"""§5.2: the optimized two-stage algorithm must match the naive oracle."""

import numpy as np
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (pip install .[test])")
from hypothesis import given, settings, strategies as st

from repro.core.application import apply_updates, apply_updates_naive
from repro.core.dsm import decode_column, encode_column
from repro.core.nsm import make_entries


def _mk_updates(rows, values, ops):
    n = len(rows)
    return make_entries(np.arange(n, dtype=np.int64),
                        np.array(ops, dtype=np.int8),
                        np.array(values, dtype=np.int32),
                        np.array(rows, dtype=np.int64),
                        np.zeros(n, dtype=np.int32))


@settings(max_examples=30, deadline=None)
@given(st.data())
def test_optimized_equals_naive(data):
    n = data.draw(st.integers(4, 200))
    base = data.draw(st.lists(st.integers(0, 500), min_size=n, max_size=n))
    m = data.draw(st.integers(1, 64))
    rows = data.draw(st.lists(st.integers(0, n - 1), min_size=m, max_size=m))
    vals = data.draw(st.lists(st.integers(0, 500), min_size=m, max_size=m))
    col = encode_column(np.array(base, dtype=np.int32))
    ups = _mk_updates(rows, vals, [1] * m)
    got = apply_updates(col, ups)
    ref = apply_updates_naive(col, ups)
    np.testing.assert_array_equal(np.asarray(decode_column(got)),
                                  np.asarray(decode_column(ref)))
    np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(ref.valid))


def test_commit_order_last_writer_wins():
    col = encode_column(np.array([10, 20, 30], dtype=np.int32))
    # two modifies to row 1; higher commit id must win
    ups = _mk_updates([1, 1], [111, 222], [1, 1])
    out = apply_updates(col, ups)
    assert int(decode_column(out)[1]) == 222


def test_insert_and_delete():
    col = encode_column(np.array([1, 2, 3], dtype=np.int32))
    ups = _mk_updates([3, 4, 0], [7, 8, 0], [2, 2, 3])  # insert r3,r4; del r0
    out = apply_updates(col, ups)
    vals = np.asarray(decode_column(out))
    valid = np.asarray(out.valid)
    assert vals[3] == 7 and vals[4] == 8
    assert not valid[0] and valid[1] and valid[3] and valid[4]


def test_dictionary_superset_and_version_bump():
    col = encode_column(np.array([5, 6], dtype=np.int32))
    ups = _mk_updates([0], [99], [1])
    out = apply_updates(col, ups)
    assert out.version == col.version + 1
    assert set(np.asarray(col.dictionary)) <= set(np.asarray(out.dictionary))
    assert 99 in set(np.asarray(out.dictionary))


# ---------------------------------------------------------------------------
# Stage 3 on the device: a resident column's compare-and-add re-encode
# ---------------------------------------------------------------------------

N_ROWS = 3000        # not a multiple of the kernel's 128-lane rows


def _stage3_case(case, rng):
    """(base values, update batch) of one device stage-3 case."""
    base = rng.choice(np.arange(100, 100_000, 10), N_ROWS).astype(np.int32)
    old = np.unique(base)
    rows = rng.choice(N_ROWS, 300, replace=False)
    if case == "no_new_values":
        return base, _mk_updates(rows[:40], rng.choice(old, 40), [1] * 40)
    if case == "new_below_between_above":
        vals = [5, 7, 155, 156, 12_345, 99_991, 100_500, 200_000]
        return base, _mk_updates(rows[:8], vals, [1] * 8)
    if case == "duplicate_rows_last_writer_wins":
        r = [rows[0], rows[1], rows[0], rows[2], rows[0], rows[1]]
        return base, _mk_updates(r, [1, 2, 3, 4, 5, 6], [1] * 6)
    if case == "write_then_delete":
        return base, _mk_updates([rows[0], rows[0], rows[1]],
                                 [77, 0, 88], [1, 3, 1])
    if case.startswith("thresholds_"):
        # that many genuinely new values (odd, so none is in `old`),
        # spread over and beyond the old dictionary
        k = int(case.split("_")[1])
        vals = rng.choice(np.arange(1, 200_001, 2), k, replace=False)
        return base, _mk_updates(rows[:k], vals, [1] * k)
    assert case == "insert_batch"
    return base, _mk_updates([N_ROWS, N_ROWS + 1, rows[0]], [9, 10, 0],
                             [2, 2, 3])


@pytest.fixture
def interpret():
    from repro.kernels import common

    common.set_interpret_override("1")
    yield
    common.set_interpret_override(None)


@pytest.mark.parametrize("case", [
    "no_new_values", "new_below_between_above",
    "duplicate_rows_last_writer_wins", "write_then_delete",
    "thresholds_8", "thresholds_9", "thresholds_256", "thresholds_257",
    "insert_batch"])
def test_device_stage3_matches_host_reencode(case, interpret):
    """A device-resident column's stage 3 (the Pallas compare-and-add, in
    interpret mode, then the row ops) is bit-identical to the host's
    ``old_to_new[old_codes]`` followed by `_apply_row_ops`; an insert
    batch takes the host path."""
    import jax

    from repro.core.application import (_apply_row_ops,
                                        _merge_dictionary_stages,
                                        _sorted_write_ops, _split_ops,
                                        apply_updates_where)
    from repro.core.backend import get_backend
    from repro.core.consistency import BuildSide
    from repro.core.dsm import EncodedColumn, new_values

    base, ups = _stage3_case(case, np.random.default_rng(7))
    host = encode_column(base)
    host.valid[::97] = False
    resident = EncodedColumn(codes=jax.device_put(host.codes),
                             dictionary=host.dictionary,
                             valid=jax.device_put(host.valid))
    pallas = get_backend("pallas")
    assert apply_updates_where(host, ups, backend=pallas)[1] == "host"
    assert apply_updates_where(resident, ups, backend="numpy")[1] == "host"

    # the host stage 3, written out
    mods, ins, dels = _split_ops(ups)
    upd, new_dict, encode, old_to_new = _merge_dictionary_stages(
        get_backend("numpy"), host.dictionary,
        np.concatenate([mods["value"], ins["value"]]))
    write_ops = _sorted_write_ops(mods, ins)
    want_codes, want_valid = _apply_row_ops(
        old_to_new[host.codes].astype(np.int32), host.valid.copy(),
        new_dict, mods, ins, dels, encode=encode,
        write_set=(write_ops, encode(write_ops["value"])))

    got, where = apply_updates_where(resident, ups, backend=pallas)
    assert where == ("host" if case == "insert_batch" else "device")
    assert isinstance(got.codes, jax.Array)
    np.testing.assert_array_equal(np.asarray(got.dictionary), new_dict)
    np.testing.assert_array_equal(np.asarray(got.codes), want_codes)
    np.testing.assert_array_equal(np.asarray(got.valid), want_valid)
    assert got.version == host.version + 1
    # the join's build side follows the column from the batch alone
    side = BuildSide(host)
    side.follow(got, ups)
    assert side.version == got.version
    np.testing.assert_array_equal(
        side.counts,
        np.bincount(want_codes[want_valid], minlength=len(new_dict)))

    # the monotone map over the whole old dictionary
    t, _ = new_values(host.dictionary, upd)
    k = np.arange(len(host.dictionary))
    np.testing.assert_array_equal(
        k + np.searchsorted(t, k, side="right"), old_to_new)
    if case.startswith("thresholds_"):
        assert len(t) == int(case.split("_")[1])


def test_code_counts_follow_a_chain_of_device_applies():
    """A resident column's build-side code counts (`BuildSide`) stay
    equal to a bincount of its valid codes across many applies with
    writes, deletes, inserts and new values, and each version keeps its
    own counts."""
    import jax

    from repro.core.consistency import BuildSide
    from repro.core.dsm import EncodedColumn

    rng = np.random.default_rng(11)
    host = encode_column(rng.integers(0, 50, N_ROWS).astype(np.int32))
    col = EncodedColumn(codes=jax.device_put(host.codes),
                        dictionary=host.dictionary,
                        valid=jax.device_put(host.valid))
    side = BuildSide(col)
    first = side.counts
    n = N_ROWS
    for i in range(40):
        m = int(rng.integers(1, 40))
        ops = rng.choice([1, 1, 1, 3], m)
        rows = rng.integers(0, n, m)
        if i % 10 == 9:                      # an insert batch now and then
            ops[:2], rows[:2] = 2, [n, n + 1]
            n += 2
        batch = _mk_updates(rows, rng.integers(0, 10_000, m), ops)
        col = apply_updates(col, batch, backend="pallas")
        side.follow(col, batch)
        codes, valid = np.asarray(col.codes), np.asarray(col.valid)
        np.testing.assert_array_equal(
            side.counts, np.bincount(codes[valid], minlength=col.dict_size))
    np.testing.assert_array_equal(
        first, np.bincount(host.codes[host.valid], minlength=host.dict_size))
