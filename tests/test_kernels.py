"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bitonic_sort import sort_1024, sort_rows
from repro.kernels.decode_attn import decode_attention
from repro.kernels.decode_attn.ref import decode_attention_ref
from repro.kernels.dict_ops import scan_filter_agg
from repro.kernels.dict_ops.ref import scan_filter_agg_ref
from repro.kernels.hash_probe import build_table, probe
from repro.kernels.merge_runs import merge_sorted_pair, merge_sorted_runs
from repro.kernels.selective_scan import selective_scan
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.snapshot_copy import snapshot_copy
from repro.kernels.snapshot_copy.ref import snapshot_copy_ref


@pytest.mark.parametrize("rows,width", [(8, 128), (16, 1024), (3, 100),
                                        (1, 1024), (5, 513)])
@pytest.mark.parametrize("dtype", [np.int32, np.float32])
def test_bitonic_sort_sweep(rng, rows, width, dtype):
    x = rng.integers(-1000, 1000, size=(rows, width)).astype(dtype)
    got = np.asarray(sort_rows(jnp.asarray(x)))
    np.testing.assert_array_equal(got, np.sort(x, axis=-1))


def test_sort_1024_unit_is_sized_like_the_paper(rng):
    v = rng.integers(0, 1 << 20, size=1024).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(sort_1024(jnp.asarray(v))),
                                  np.sort(v))
    with pytest.raises(AssertionError):
        sort_1024(jnp.zeros(2048, jnp.int32))


@pytest.mark.parametrize("k,length", [(2, 128), (4, 100), (8, 333), (3, 50)])
def test_merge_runs_sweep(rng, k, length):
    runs = [np.sort(rng.integers(0, 10**6, size=length).astype(np.int32))
            for _ in range(k)]
    mk, mi = merge_sorted_runs([jnp.asarray(r) for r in runs])
    cat = np.concatenate(runs)
    valid = np.asarray(mi) >= 0
    got = np.asarray(mk)[valid]
    np.testing.assert_array_equal(got, np.sort(cat))
    np.testing.assert_array_equal(cat[np.asarray(mi)[valid]], got)


@pytest.mark.parametrize("span", [(0, 1 << 20),                  # int32 range
                                  (1 << 31, 1 << 40),            # > 2^31
                                  (-(1 << 40), 1 << 40)])        # negative too
def test_merge_runs_int64_keys(rng, span):
    """The comparator tree merges full int64 keys ((hi, lo) int32 lanes)."""
    lo, hi = span
    keys = np.unique(rng.integers(lo, hi, size=512, dtype=np.int64))
    rng.shuffle(keys)
    runs = [np.sort(keys[t::3]) for t in range(3)]
    mk, mi = merge_sorted_runs(runs)
    cat = np.concatenate(runs)
    valid = np.asarray(mi) >= 0
    got = np.asarray(mk)[valid]
    np.testing.assert_array_equal(got, np.sort(keys))
    np.testing.assert_array_equal(cat[np.asarray(mi)[valid]], got)


def test_merge_runs_int64_max_key_not_dropped():
    """A real int64.max key ties with the padding sentinel — such runs must
    route to the exact reference merge instead of losing the entry."""
    top = np.iinfo(np.int64).max
    a = np.array([5, top], dtype=np.int64)
    b = np.array([7], dtype=np.int64)
    mk, mi = merge_sorted_runs([a, b])
    valid = np.asarray(mi) >= 0
    np.testing.assert_array_equal(np.asarray(mk)[valid], [5, 7, top])


@pytest.mark.parametrize("n_keys,n_queries", [(10, 64), (500, 1000),
                                              (2000, 4096)])
def test_hash_probe_sweep(rng, n_keys, n_queries):
    keys = rng.choice(1 << 20, size=n_keys, replace=False).astype(np.int32)
    vals = rng.integers(0, 1000, size=n_keys).astype(np.int32)
    t = build_table(keys, vals)
    qs = np.concatenate([keys[: n_keys // 2],
                         rng.choice(1 << 20, size=n_queries - n_keys // 2)
                         .astype(np.int32)])
    got = np.asarray(probe(t, jnp.asarray(qs), default=-7))
    kv = dict(zip(keys.tolist(), vals.tolist()))
    exp = np.array([kv.get(int(q), -7) for q in qs], dtype=np.int32)
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("n,k", [(4096, 8), (10_000, 64), (100_000, 500)])
def test_scan_filter_agg_sweep(rng, n, k):
    fcodes = rng.integers(0, k, size=n).astype(np.int32)
    acodes = rng.integers(0, k, size=n).astype(np.int32)
    valid = rng.random(n) < 0.9
    d = np.sort(rng.choice(10**6, size=k, replace=False)).astype(np.int32)
    lo, hi = k // 4, 3 * k // 4
    s, c = scan_filter_agg(jnp.asarray(fcodes), jnp.asarray(acodes),
                           jnp.asarray(valid), jnp.asarray(d), lo, hi)
    rs, rc = scan_filter_agg_ref(jnp.asarray(fcodes), jnp.asarray(acodes),
                                 jnp.asarray(valid), jnp.asarray(d), lo, hi)
    np.testing.assert_allclose(float(s), float(rs), rtol=1e-6)
    assert int(c) == int(rc)


@pytest.mark.parametrize("n_shards,width", [(1, 4096), (4, 1000), (3, 7),
                                            (8, 5000), (2, 0)])
def test_scan_filter_agg_sharded_sweep(rng, n_shards, width):
    """Leading-shard-axis fused scan: one launch == per-shard oracle,
    exactly (negative dictionary values exercise the split accumulator)."""
    from repro.kernels.dict_ops import scan_filter_agg_sharded
    from repro.kernels.dict_ops.ref import scan_filter_agg_sharded_ref
    k = 60
    fcodes = rng.integers(0, k, size=(n_shards, width)).astype(np.int32)
    acodes = rng.integers(0, k, size=(n_shards, width)).astype(np.int32)
    valid = rng.random((n_shards, width)) < 0.85
    d = np.sort(rng.choice(np.arange(-(10**6), 10**6, dtype=np.int32),
                           size=k, replace=False))
    bounds = [(k // 4, 3 * k // 4), (0, k), (7, 7)]
    got = scan_filter_agg_sharded(jnp.asarray(fcodes), jnp.asarray(acodes),
                                  jnp.asarray(valid), jnp.asarray(d), bounds)
    assert got == scan_filter_agg_sharded_ref(fcodes, acodes, valid, d,
                                              bounds)


def test_probe_sharded_matches_per_island_probe(rng):
    """Leading-batch-axis probe (ragged islands stack-padded): elementwise
    identical to one probe call per island."""
    from repro.kernels.hash_probe import probe_sharded
    keys = rng.choice(1 << 20, size=300, replace=False).astype(np.int32)
    vals = rng.integers(0, 1000, size=300).astype(np.int32)
    t = build_table(keys, vals)
    batches = [rng.choice(np.concatenate([keys, rng.integers(0, 1 << 20, m)
                                          .astype(np.int32)]), size=m)
               .astype(np.int32) if m else np.empty(0, np.int32)
               for m in (0, 3, 700, 64)]
    got = probe_sharded(t, batches, default=-7)
    for b, g in zip(batches, got):
        exp = (np.asarray(probe(t, jnp.asarray(b), default=-7))
               if len(b) else np.empty(0, np.int32))
        np.testing.assert_array_equal(g, exp)


@pytest.mark.parametrize("n,block", [(50_000, 8192), (8192, 1024),
                                     (1000, 256)])
def test_snapshot_copy_sweep(rng, n, block):
    src = rng.integers(0, 100, size=n).astype(np.int32)
    prev = rng.integers(0, 100, size=n).astype(np.int32)
    n_chunks = (n + block - 1) // block
    dirty = rng.integers(0, 2, size=n_chunks).astype(np.int32)
    got = np.asarray(snapshot_copy(jnp.asarray(src), jnp.asarray(prev),
                                   jnp.asarray(dirty), block=block))
    exp = np.asarray(snapshot_copy_ref(jnp.asarray(src), jnp.asarray(prev),
                                       jnp.asarray(dirty), block))
    np.testing.assert_array_equal(got, exp)


@pytest.mark.parametrize("B,T,D,N", [(1, 256, 128, 8), (2, 512, 256, 16)])
def test_selective_scan_sweep(rng, B, T, D, N):
    x = jnp.asarray(rng.normal(size=(B, T, D)).astype(np.float32))
    dt = jnp.asarray(np.abs(rng.normal(size=(B, T, D))).astype(np.float32)
                     * 0.1)
    a = jnp.asarray(-np.abs(rng.normal(size=(D, N))).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(B, T, N)).astype(np.float32))
    d = jnp.asarray(rng.normal(size=(D,)).astype(np.float32))
    got = selective_scan(x, dt, a, b, c, d, d_block=min(128, D),
                         t_block=min(256, T))
    ref = selective_scan_ref(x, dt, a, b, c, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("H,Hkv,S,L,cap", [(8, 2, 1024, 777, 0.0),
                                           (4, 4, 2048, 2048, 0.0),
                                           (8, 1, 512, 100, 50.0)])
def test_decode_attention_sweep(rng, H, Hkv, S, L, cap):
    B, d = 2, 64
    q = jnp.asarray(rng.normal(size=(B, H, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, d)).astype(np.float32))
    got = decode_attention(q, k, v, jnp.int32(L), softcap=cap)
    ref = decode_attention_ref(q, k, v, L, d ** -0.5, softcap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_flash_attention_matches_sdpa(rng):
    from repro.nn.attention import _sdpa, causal_mask
    from repro.nn.flash import flash_attention
    B, S, H, Hkv, dh = 2, 2048, 4, 2, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, dh)).astype(np.float32))
    for kw in [dict(causal=True), dict(causal=True, window=256),
               dict(causal=False), dict(causal=True, softcap=30.0)]:
        got = flash_attention(q, k, v, **kw)
        m = causal_mask(S, kw.get("window", 0))[:, None] if kw["causal"] \
            else jnp.ones((1, 1, S, S), bool)
        ref = _sdpa(q, k, v, m, kw.get("softcap", 0.0))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("size", ["one", "select_max", "past_select",
                                  "mid", "pad_min", "past_pad_min"])
@pytest.mark.parametrize("on_device", [False, True], ids=["host", "device"])
def test_dictionary_pad_widths(size, on_device):
    """A scan's dictionary operand pads to the next power of two inside
    XLA's compare/select range, and past it to at least `DICT_PAD_MIN`
    entries; the values come first, the pad is zeros, host numpy stays
    host numpy."""
    from repro.kernels.common import next_pow2
    from repro.kernels.dict_ops import ops

    smax, pmin = ops.DICT_SELECT_MAX, ops.DICT_PAD_MIN
    k = {"one": 1, "select_max": smax, "past_select": smax + 1,
         "mid": 4097, "pad_min": pmin, "past_pad_min": pmin + 1}[size]
    want = {"one": 1, "select_max": smax, "past_select": pmin,
            "mid": pmin, "pad_min": pmin,
            "past_pad_min": next_pow2(pmin + 1)}[size]
    d = np.arange(1, k + 1, dtype=np.int32)
    out = ops.pad_dictionary_pow2(jnp.asarray(d) if on_device else d)
    assert isinstance(out, jax.Array if on_device else np.ndarray)
    out = np.asarray(out)
    assert out.shape == (want,)
    np.testing.assert_array_equal(out[:k], d)
    assert not out[k:].any()
