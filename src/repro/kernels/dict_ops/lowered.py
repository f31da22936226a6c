"""Jitted jax-numpy lowerings of the fused-scan kernels (CPU fast path).

Each lowering computes the *same per-block split-16-bit int32 partials* as
its Pallas kernel — pure integer arithmetic, so the results are
bit-identical and the ops-layer host reassembly is shared verbatim between
the kernel and lowered paths. The bodies are plain traceable functions
(no jit) so the fused join-scan entry point in ``kernels/hash_probe`` can
inline two of them inside ONE traced call; the jitted wrappers here are
the standalone entry points the ops wrappers dispatch to on CPU.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels.bitonic_sort.bitonic_sort import (_bitonic_merge_network,
                                                     _bitonic_network)
from repro.kernels.common import instrumented_jit, next_pow2


def scan_exact_partials(fcodes, acodes, valid, dictionary, bounds, block):
    """Traceable body: (lo16, hi16, cnt, neg) per-block partials, (nb, Q).

    Mirrors ``dict_ops._scan_exact_kernel`` exactly: per-block masked sums
    of the split 16-bit halves of the two's-complement aggregate values,
    each partial bounded by block * 0xFFFF < 2^31.
    """
    n = fcodes.shape[0]
    nb = n // block
    f = fcodes.reshape(nb, block)
    a = acodes.reshape(nb, block)
    v = valid.reshape(nb, block)
    lo = bounds[:, 0][:, None, None]
    hi = bounds[:, 1][:, None, None]
    mask = (f[None] >= lo) & (f[None] < hi) & (v[None] != 0)
    m = mask.astype(jnp.int32)                    # (Q, nb, block)
    vals = jnp.take(dictionary, a)                # (nb, block)
    lo16 = (vals & 0xFFFF)[None]
    hi16 = ((vals >> 16) & 0xFFFF)[None]
    neg = (vals < 0).astype(jnp.int32)[None]
    return (jnp.sum(m * lo16, axis=2).T,          # (nb, Q) each
            jnp.sum(m * hi16, axis=2).T,
            jnp.sum(m, axis=2).T,
            jnp.sum(m * neg, axis=2).T)


def scan_exact_sharded_partials(fcodes, acodes, valid, dictionary, bounds,
                                block):
    """Traceable body: (n_shards, nb, Q) partials — the stacked-shard scan."""
    n_shards, width = fcodes.shape
    nb = width // block
    f = fcodes.reshape(n_shards, nb, block)
    a = acodes.reshape(n_shards, nb, block)
    v = valid.reshape(n_shards, nb, block)
    lo = bounds[:, 0][:, None, None, None]
    hi = bounds[:, 1][:, None, None, None]
    mask = (f[None] >= lo) & (f[None] < hi) & (v[None] != 0)
    m = mask.astype(jnp.int32)                    # (Q, S, nb, block)
    vals = jnp.take(dictionary, a)                # (S, nb, block)
    lo16 = (vals & 0xFFFF)[None]
    hi16 = ((vals >> 16) & 0xFFFF)[None]
    neg = (vals < 0).astype(jnp.int32)[None]
    move = functools.partial(jnp.transpose, axes=(1, 2, 0))
    return (move(jnp.sum(m * lo16, axis=3)),      # (S, nb, Q) each
            move(jnp.sum(m * hi16, axis=3)),
            move(jnp.sum(m, axis=3)),
            move(jnp.sum(m * neg, axis=3)))


def scan_values_partials(fvals, avals, valid, bounds, block):
    """Traceable body: raw-value correction-scan partials, (nb, Q).

    Mirrors ``dict_ops._scan_values_kernel`` exactly: bounds are INCLUSIVE
    value ranges and the aggregate sums `avals` directly (no dictionary
    take) — the delta-overlay correction pass. Same split-16-bit int32
    partials, each bounded by block * 0xFFFF < 2^31.
    """
    n = fvals.shape[0]
    nb = n // block
    f = fvals.reshape(nb, block)
    a = avals.reshape(nb, block)
    v = valid.reshape(nb, block)
    lo = bounds[:, 0][:, None, None]
    hi = bounds[:, 1][:, None, None]
    mask = (f[None] >= lo) & (f[None] <= hi) & (v[None] != 0)
    m = mask.astype(jnp.int32)                    # (Q, nb, block)
    lo16 = (a & 0xFFFF)[None]
    hi16 = ((a >> 16) & 0xFFFF)[None]
    neg = (a < 0).astype(jnp.int32)[None]
    return (jnp.sum(m * lo16, axis=2).T,          # (nb, Q) each
            jnp.sum(m * hi16, axis=2).T,
            jnp.sum(m, axis=2).T,
            jnp.sum(m * neg, axis=2).T)


def pad_rows_flat(fcodes, acodes, valid, block):
    """In-trace row padding to a block multiple (valid=0 scan identity;
    fcodes get int32.max so no code range matches). Traced shapes key on
    the RAW row count, so callers skip the eager pad dispatches — the
    expensive part of per-call overhead on CPU (~35us per eager op)."""
    n = fcodes.shape[0]
    pad = (-n) % block
    v = valid.astype(jnp.int32)
    if pad:
        fcodes = jnp.pad(fcodes, (0, pad),
                         constant_values=jnp.iinfo(jnp.int32).max)
        acodes = jnp.pad(acodes, (0, pad))
        v = jnp.pad(v, (0, pad))
    return fcodes, acodes, v


def pad_rows_sharded(fcodes, acodes, valid, block):
    """In-trace width padding of stacked (n_shards, width) shards."""
    width = fcodes.shape[1]
    pad = (-width) % block
    v = valid.astype(jnp.int32)
    if pad:
        wpad = ((0, 0), (0, pad))
        fcodes = jnp.pad(fcodes, wpad)
        acodes = jnp.pad(acodes, wpad)
        v = jnp.pad(v, wpad)
    return fcodes, acodes, v


@functools.partial(instrumented_jit, static_argnames=("block",))
def scan_exact_lowered(fcodes, acodes, valid, dictionary, bounds,
                       block: int = 4096):
    fcodes, acodes, v = pad_rows_flat(fcodes, acodes, valid, block)
    return scan_exact_partials(fcodes, acodes, v, dictionary, bounds, block)


@functools.partial(instrumented_jit, static_argnames=("block",))
def scan_exact_sharded_lowered(fcodes, acodes, valid, dictionary, bounds,
                               block: int = 4096):
    fcodes, acodes, v = pad_rows_sharded(fcodes, acodes, valid, block)
    return scan_exact_sharded_partials(fcodes, acodes, v, dictionary,
                                       bounds, block)


@functools.partial(instrumented_jit, static_argnames=("block",))
def scan_values_lowered(fvals, avals, valid, bounds, block: int = 4096):
    """Jitted raw-value correction scan; callers pre-pad rows to a block
    multiple on the host (overlay sizes vary per query group, so pow2
    bucketing happens there to bound the traced shapes)."""
    return scan_values_partials(fvals, avals, valid.astype(jnp.int32),
                                bounds, block)


# ---------------------------------------------------------------------------
# Fused pipelines (PR 9): whole query groups and whole ship-batch apply
# stages as ONE traced program each. The bodies below compose the partial
# helpers above so a group's base scan and its delta-overlay corrections
# (or a ship batch's sort + dictionary merge) share a single jitted
# dispatch instead of a chain of per-kernel launches.
# ---------------------------------------------------------------------------

def scan_group_partials(fcodes, acodes, valid, dictionary, bounds, corr,
                        vbounds, block, cblock):
    """Traceable body: one no-join query group INCLUDING its delta
    correction. `corr` is a (6, nr) int32 stack of
    [fv_eff, av_eff, valid_eff, fv_base, av_base, valid_base] overlay rows
    (host pow2-padded, valid=0 pad); `bounds` are EXCLUSIVE code ranges for
    the base scan, `vbounds` INCLUSIVE raw-value ranges for the correction
    scans. Returns 12 partial arrays: base + effective + base-state, each a
    (lo16, hi16, cnt, neg) quadruple the host folds as base + eff - state.
    """
    fcodes, acodes, v = pad_rows_flat(fcodes, acodes, valid, block)
    base = scan_exact_partials(fcodes, acodes, v, dictionary, bounds, block)
    eff = scan_values_partials(corr[0], corr[1], corr[2], vbounds, cblock)
    neg = scan_values_partials(corr[3], corr[4], corr[5], vbounds, cblock)
    return base + eff + neg


def scan_group_sharded_partials(fcodes, acodes, valid, dictionary, bounds,
                                corr, vbounds, block, cblock):
    """Sharded sibling of `scan_group_partials`: the base scan runs over the
    stacked (n_shards, width) resident shards, the correction scans over the
    flat overlay stack (overlays are global, not sharded). Returns 4 sharded
    (S, nb, Q) partials followed by 8 flat (nb, Q) correction partials."""
    fcodes, acodes, v = pad_rows_sharded(fcodes, acodes, valid, block)
    base = scan_exact_sharded_partials(fcodes, acodes, v, dictionary, bounds,
                                       block)
    eff = scan_values_partials(corr[0], corr[1], corr[2], vbounds, cblock)
    neg = scan_values_partials(corr[3], corr[4], corr[5], vbounds, cblock)
    return base + eff + neg


def scan_values_delta_partials(corr, vbounds, cblock):
    """Traceable body: effective + base-state correction scans of one
    (6, nr) overlay stack in a single program — 8 partial arrays."""
    eff = scan_values_partials(corr[0], corr[1], corr[2], vbounds, cblock)
    neg = scan_values_partials(corr[3], corr[4], corr[5], vbounds, cblock)
    return eff + neg


def apply_sort_merge(old, vals):
    """Traceable body: the ship-batch apply pipeline's device half.

    `old` is (rows, w_old) int32 — each column's OLD dictionary (sorted
    ascending, int32.max sentinel pad); `vals` is (rows, w_val) raw update
    values (sentinel pad). The widths are INDEPENDENT pow2 buckets, so the
    sort network runs at the (usually much smaller) update-value width
    instead of being dragged up to the dictionary width. Each row sorts its
    values with the full bitonic network, then merges them with the old
    dictionary through the half-cleaner merge network: ascending old row ++
    all-sentinel gap ++ reversed sorted values is ascending-then-descending
    — bitonic — at the next pow2 of (w_old + w_val), which the merge
    network sorts in log2(w_merge) stages. Returns (sorted_vals
    (rows, w_val), merged (rows, w_merge)); sentinels sort to the tail of
    both, so the host slices real entries by length.
    """
    rows, w_old = old.shape
    w_val = vals.shape[1]
    svals = _bitonic_network(vals)
    w_merge = next_pow2(w_old + w_val)
    parts = [old]
    gap = w_merge - w_old - w_val
    if gap:
        parts.append(jnp.full((rows, gap), jnp.iinfo(jnp.int32).max,
                              dtype=old.dtype))
    parts.append(svals[:, ::-1])
    return svals, _bitonic_merge_network(jnp.concatenate(parts, axis=1))


# Jitted fused entry points. The query-group programs return per-block
# partials, so no output can alias the per-call correction stack and it is
# not donated; the apply pipeline's sorted values alias its value stack, so
# that one has a donated twin, selected in the ops wrapper via
# common.donation_enabled() — donated only in compiled mode, where XLA
# honors donation (XLA:CPU ignores it and warns). Both twins share one
# trace-count label, so the zero-retrace accounting is donation-agnostic.

scan_group_lowered = functools.partial(instrumented_jit,
                                       static_argnames=("block", "cblock"),
                                       name="scan_group_lowered")(
    scan_group_partials)

scan_group_sharded_lowered = functools.partial(
    instrumented_jit, static_argnames=("block", "cblock"),
    name="scan_group_sharded_lowered")(scan_group_sharded_partials)

scan_values_delta_lowered = functools.partial(
    instrumented_jit, static_argnames=("cblock",),
    name="scan_values_delta_lowered")(scan_values_delta_partials)

apply_pipeline_lowered = instrumented_jit(
    apply_sort_merge, name="apply_pipeline_lowered")
apply_pipeline_lowered_donated = instrumented_jit(
    apply_sort_merge, donate_argnums=(1,), name="apply_pipeline_lowered")
