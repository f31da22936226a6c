"""Public wrappers for the merge unit (k-way merge as a comparator tree).

Keys are full-width int64 commit ids. Because the TPU comparator network
works on int32 lanes (and the host JAX session runs without x64), each key
is split into an arithmetic high word and a bias-corrected low word whose
lexicographic (hi, lo) order equals int64 order; the kernel merges the
lanes and the results are recombined here. This removes the old int32-only
restriction (and its numpy fallback): commit ids beyond 2^31 merge on the
kernel path like any others.
"""

from __future__ import annotations

import numpy as np

from repro.kernels.common import kernel_mode, next_pow2
from repro.kernels.merge_runs.merge_runs import (bitonic_merge_pair,
                                                 merge_lanes_lowered)
from repro.kernels.merge_runs.ref import merge_pair_ref, merge_runs_ref

_BIAS = np.int64(1) << np.int64(31)
_LO_MASK = (np.int64(1) << np.int64(32)) - np.int64(1)
# The padding sentinel (int32.max, int32.max) recombines to int64.max, so a
# *real* int64.max key would tie with padding and could be trimmed away.
# Runs containing it take the exact reference merge instead (the one key
# value the comparator network cannot distinguish from padding).
_SENTINEL_KEY = np.iinfo(np.int64).max


def _split64(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """int64 keys -> (hi, lo) int32 lanes with (hi, lo) lex order == key order.

    hi is the arithmetic high word (sign-preserving shift); lo is the low
    word re-biased from [0, 2^32) into signed int32 range so its signed
    comparison matches the unsigned low-word order.
    """
    v = np.asarray(keys, dtype=np.int64)
    hi = (v >> np.int64(32)).astype(np.int32)
    lo = ((v & _LO_MASK) - _BIAS).astype(np.int32)
    return hi, lo


def _join64(hi: np.ndarray, lo: np.ndarray) -> np.ndarray:
    """Inverse of _split64."""
    lo_u = (lo.astype(np.int64) + _BIAS) & _LO_MASK
    return (hi.astype(np.int64) << np.int64(32)) | lo_u


_I32_MAX = np.iinfo(np.int32).max


def _merge_lane_pair(ah, al, ai, bh, bl, bi):
    """Merge two ascending (rows, w) host-numpy lane triples -> trimmed
    host-numpy (rows, wa+wb).

    Pads runs to a shared power-of-two width with (hi, lo) = int32-max
    sentinels that sort after every real key except a literal int64.max
    (callers route runs containing it to the reference merge); sentinel
    entries carry index -1 and are trimmed off the tail.

    All padding and trimming happens in host numpy: the lowered path stacks
    the six lanes into ONE (6, rows, width) buffer so a warm merge costs a
    single jitted dispatch (each eager device pad/slice is ~35-80us on CPU,
    and a tournament round issues many). The merge network is
    row-independent, so the lowered path needs no rows%8 padding — that
    exists only for the kernel's row tiling.
    """
    rows, wa = ah.shape
    wb = bh.shape[-1]
    width = next_pow2(max(wa, wb, 128))
    mode = kernel_mode()
    if mode == "lowered":
        lanes = np.full((6, rows, width), _I32_MAX, dtype=np.int32)
        lanes[2] = -1
        lanes[5] = -1
        lanes[0, :, :wa] = ah
        lanes[1, :, :wa] = al
        lanes[2, :, :wa] = ai
        lanes[3, :, :wb] = bh
        lanes[4, :, :wb] = bl
        lanes[5, :, :wb] = bi
        oh, ol, oi = np.asarray(merge_lanes_lowered(lanes))
        return oh[:, : wa + wb], ol[:, : wa + wb], oi[:, : wa + wb]
    pad_rows = (-rows) % 8
    padded = []
    for lane, wlane, fill in ((ah, wa, _I32_MAX), (al, wa, _I32_MAX),
                              (ai, wa, -1), (bh, wb, _I32_MAX),
                              (bl, wb, _I32_MAX), (bi, wb, -1)):
        buf = np.full((rows + pad_rows, width), fill, dtype=np.int32)
        buf[:rows, :wlane] = lane
        padded.append(buf)
    oh, ol, oi = bitonic_merge_pair(*padded,
                                    interpret=(mode == "interpret"))
    # valid entries sort before the sentinels; trim to true length
    return (np.asarray(oh)[:rows, : wa + wb],
            np.asarray(ol)[:rows, : wa + wb],
            np.asarray(oi)[:rows, : wa + wb])


def merge_sorted_pair(a, b, ai, bi, use_pallas: bool = True):
    """Merge two ascending (rows, w) key runs -> (rows, 2w) with indices.

    Keys may be any integer dtype up to int64; the output keys come back as
    int64 (exact — recombined from the merged lanes).
    """
    a64 = np.asarray(a, dtype=np.int64)
    b64 = np.asarray(b, dtype=np.int64)
    ai = np.asarray(ai, dtype=np.int32)
    bi = np.asarray(bi, dtype=np.int32)
    if not use_pallas or (a64.size and a64.max() == _SENTINEL_KEY) \
            or (b64.size and b64.max() == _SENTINEL_KEY):
        return merge_pair_ref(a64, b64, ai, bi)
    ah, al = _split64(a64)
    bh, bl = _split64(b64)
    oh, ol, oi = _merge_lane_pair(ah, al, ai, bh, bl, bi)
    return _join64(oh, ol), oi


def merge_sorted_runs(runs: list, use_pallas: bool = True):
    """K-way merge (the 8-queue comparator tree): pairwise tournament.

    runs: list of 1-D ascending integer key arrays (per-thread update logs;
    int64 commit ids are first-class). Returns (merged_keys int64,
    merged_source_index int32) where source index is the position in the
    concatenated input — ops callers gather payloads with it.
    """
    runs64 = [np.asarray(r, dtype=np.int64).reshape(-1) for r in runs]
    offsets = np.cumsum([0] + [r.shape[0] for r in runs64[:-1]])
    if not use_pallas or any(r.size and r[-1] == _SENTINEL_KEY
                             for r in runs64):  # runs are ascending
        return merge_runs_ref(runs64)
    if kernel_mode() == "lowered":
        # Measured on XLA:CPU the jitted comparator tournament loses to the
        # host k-way merge at every run size (the dispatch alone costs ~10x
        # the merge for ship-batch-sized logs, and numpy's argsort keeps
        # winning well past 64k entries), so the lowered tier takes the
        # exact host reference; interpret/compiled keep the kernel tree.
        return merge_runs_ref(runs64)
    # kernel modes: pairwise tournament, one kernel dispatch per pair
    keyed = []
    for r, off in zip(runs64, offsets):
        hi, lo = _split64(r)
        idx = (np.arange(r.shape[0], dtype=np.int32) + np.int32(off))
        keyed.append((hi[None, :], lo[None, :], idx[None, :]))
    while len(keyed) > 1:
        nxt = []
        for p in range(0, len(keyed) - 1, 2):
            (ah, al, ai), (bh, bl, bi) = keyed[p], keyed[p + 1]
            nxt.append(_merge_lane_pair(ah, al, ai, bh, bl, bi))
        if len(keyed) % 2:
            nxt.append(keyed[-1])
        keyed = nxt
    hi, lo, idx = keyed[0]
    return _join64(hi[0], lo[0]), idx[0]


def merge_sorted_pairs(a_list, b_list, use_pallas: bool = True):
    """Merge C independent ascending (a_i, b_i) run pairs in ONE merge
    dispatch: pair i rides row i of the row-independent merge network.

    Values only — no payload indices come back. Returns the merged int64
    key arrays, each of exact length len(a_i) + len(b_i), elementwise
    identical to C separate two-run merges: a merged key sequence is
    determined by its input multiset, and each row's sentinel padding
    sorts to that row's tail.
    """
    a64 = [np.asarray(a, dtype=np.int64).reshape(-1) for a in a_list]
    b64 = [np.asarray(b, dtype=np.int64).reshape(-1) for b in b_list]
    if not use_pallas or any(r.size and r[-1] == _SENTINEL_KEY
                             for r in a64 + b64):  # runs are ascending
        return [merge_runs_ref([a, b])[0] for a, b in zip(a64, b64)]
    rows = len(a64)
    wa = max(max((a.shape[0] for a in a64), default=0), 1)
    wb = max(max((b.shape[0] for b in b64), default=0), 1)
    ah = np.full((rows, wa), _I32_MAX, dtype=np.int32)
    al = np.full((rows, wa), _I32_MAX, dtype=np.int32)
    ai = np.full((rows, wa), -1, dtype=np.int32)
    bh = np.full((rows, wb), _I32_MAX, dtype=np.int32)
    bl = np.full((rows, wb), _I32_MAX, dtype=np.int32)
    bi = np.full((rows, wb), -1, dtype=np.int32)
    for i, (a, b) in enumerate(zip(a64, b64)):
        na, nb = a.shape[0], b.shape[0]
        ah[i, :na], al[i, :na] = _split64(a)
        ai[i, :na] = np.arange(na, dtype=np.int32)
        bh[i, :nb], bl[i, :nb] = _split64(b)
        bi[i, :nb] = np.arange(nb, dtype=np.int32)
    oh, ol, _ = _merge_lane_pair(ah, al, ai, bh, bl, bi)
    merged = _join64(oh, ol)
    return [merged[i, :a64[i].shape[0] + b64[i].shape[0]]
            for i in range(rows)]


