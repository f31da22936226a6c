"""The seeded traffic is deterministic and offers every seed the same work."""

import numpy as np
import pytest

from chipbench import gen

SEEDS = (7, 2**31 + 12345)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_inputs(seed):
    a = gen.gen_table(seed, 1000, 8, 32, 1 << 24)
    b = gen.gen_table(seed, 1000, 8, 32, 1 << 24)
    np.testing.assert_array_equal(a, b)
    s1 = gen.TxnStream(seed, 1000, 8, 1 << 24, 0.5, 4)
    s2 = gen.TxnStream(seed, 1000, 8, 1 << 24, 0.5, 4)
    t1 = gen.Txns.concat([s1.take(100), s1.take(gen.STREAM_BLOCK)])
    t2 = s2.take(100 + gen.STREAM_BLOCK)
    for f in ("thread_id", "commit_id", "op", "row", "col", "value"):
        np.testing.assert_array_equal(getattr(t1, f), getattr(t2, f))
    q1 = gen.gen_queries(gen.rng_for(seed, 2), 50, 8, 1 << 24, 0.3, 0.5)
    q2 = gen.gen_queries(gen.rng_for(seed, 2), 50, 8, 1 << 24, 0.3, 0.5)
    assert q1 == q2
    np.testing.assert_array_equal(gen.arrival_times(3.0, 45),
                                  gen.arrival_times(3.0, 45))


def test_every_seed_offers_the_same_work():
    tables = [gen.gen_table(s, 4096, 8, 32, 1 << 24) for s in SEEDS]
    for t in tables:
        assert t.dtype == np.int32 and t.shape == (4096, 8)
        assert all(len(np.unique(t[:, j])) <= 32 for j in range(8))
    streams = [gen.TxnStream(s, 4096, 8, 1 << 24, 0.5, 4).take(
        gen.STREAM_BLOCK) for s in SEEDS]
    assert len({int((t.op == gen.OP_MODIFY).sum()) for t in streams}) == 1
    assert all(np.array_equal(t.commit_id, np.arange(gen.STREAM_BLOCK))
               for t in streams)
    qs = [gen.gen_queries(gen.rng_for(s, 2), 64, 8, 1 << 24, 0.3, 0.5)
          for s in SEEDS]
    assert {sum(q.join_col is not None for q in x) for x in qs} == {32}
    for x in qs:
        assert all(q.hi - q.lo == int(0.3 * (1 << 24)) for q in x)
    due = gen.arrival_times(2.5, 45)
    assert len(due) == 112
    assert due[0] == 0 and np.all(np.diff(due) > 0) and due[-1] < 45
    # Poisson-like: gaps spread as the exponential's quantiles do
    gaps = np.diff(due)
    assert gaps.max() > 5 * np.median(gaps)
