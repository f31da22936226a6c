"""Time a flat scan's dictionary decode alone on the chip, by path.

    python3 tools/decode_bench.py [--rows 16777216] [--reps 5]

Run from the root of a checkout, on the chip. At the padded dictionary
width a grown column scans with (`dict_ops.DICT_PAD_MIN`), for each live
dictionary length and query count it times on the same columns the
histogram path (`dict_ops._hist_counts`, then the host's int64 dot,
`ops.exact_totals`) and the decoding scan that gathers
(`dict_ops.scan_exact_decoded`, then `ops.assemble_exact`), checks both
against numpy, and prints one JSON line per case: the median milliseconds
of each path's device program (``*_ms``) and of the program, the copy of
its outputs to the host and the host's totals together (``*_total_ms``).
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import numpy as np

from repro.kernels.dict_ops import dict_ops, ops

LIVE = (1 << 12, 50_000, ops.DICT_PAD_MIN)
QUERIES = (1, 2, 4, 8, 16)


@functools.partial(jax.jit, static_argnames="width")
def _hist(f, a, v, bounds, live, width: int):
    return (dict_ops._hist_counts(bounds, f, a, v, live, width, False),)


@jax.jit
def _gather(f, a, v, dictionary, bounds):
    return dict_ops.scan_exact_decoded(f, a, v, dictionary, bounds, 4096,
                                       False)


def _median_ms(fn, reps: int):
    out = fn()          # compiles
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        out = fn()
        times.append(1e3 * (time.perf_counter() - t))
    return float(np.median(times)), out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--rows", type=int, default=1 << 24)
    p.add_argument("--reps", type=int, default=5)
    args = p.parse_args(argv)
    rng = np.random.default_rng(17)
    n, width = args.rows, ops.DICT_PAD_MIN
    f_host = rng.integers(0, 1 << 16, n, dtype=np.int32)
    v_host = rng.random(n) < 0.9
    f, v = jax.device_put(f_host), jax.device_put(v_host)
    for live in LIVE:
        a_host = rng.integers(0, live, n, dtype=np.int32)
        a = jax.device_put(a_host)
        d = rng.integers(-2**31, 2**31, live, dtype=np.int64).astype(np.int32)
        dpad = jax.device_put(ops.pad_dictionary_pow2(d))
        klive = jax.device_put(ops.live_length(d))
        vals = d.astype(np.int64)[a_host]
        for nq in QUERIES:
            lo = rng.integers(0, 1 << 15, nq)
            bounds = np.stack([lo, lo + (1 << 14)], 1).astype(np.int32)
            want = [int(vals[(f_host >= b0) & (f_host < b1) & v_host].sum())
                    for b0, b1 in bounds]
            barr = jax.device_put(bounds)
            hist_ms, _ = _median_ms(
                lambda: jax.block_until_ready(
                    _hist(f, a, v, barr, klive, width)), args.reps)
            gather_ms, _ = _median_ms(
                lambda: jax.block_until_ready(_gather(f, a, v, dpad, barr)),
                args.reps)
            hist_total_ms, (hsums, _) = _median_ms(
                lambda: ops.exact_totals(_hist(f, a, v, barr, klive, width),
                                         d), args.reps)
            gather_total_ms, (gsums, _) = _median_ms(
                lambda: ops.exact_totals(_gather(f, a, v, dpad, barr), d),
                args.reps)
            print(json.dumps({
                "rows": n, "width": width, "live": live, "queries": nq,
                "hist_ms": hist_ms, "hist_total_ms": hist_total_ms,
                "gather_ms": gather_ms, "gather_total_ms": gather_total_ms,
                "correct": (hsums.tolist() == want
                            and gsums.tolist() == want),
                "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
