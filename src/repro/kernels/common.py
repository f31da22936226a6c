"""Shared kernel utilities: runtime kernel mode + trace accounting.

Off-TPU, Pallas kernels can only run in *interpret* mode — a per-launch
Python emulation that is bit-exact but ~1000x slower than compiled code.
The kernel layer therefore resolves one of three execution modes at call
time (``kernel_mode``):

* ``"compiled"`` — real ``pallas_call`` lowering (TPU/GPU, or forced).
* ``"interpret"`` — Pallas interpret mode: the bit-exact kernel-semantics
  oracle, selectable anywhere.
* ``"lowered"``  — a jitted jax-numpy lowering of the same math (identical
  integer results, asserted by the golden-answer suite). This is the CPU
  fast path: XLA compiles it once per pow2-bucketed shape.

The choice is the ``REPRO_PALLAS_INTERPRET`` environment variable
(``0`` force-compile, ``1`` force interpret, ``auto`` — the default —
compiled on real accelerators, lowered on CPU), validated with an
actionable error in the style of ``core.backend.parse_backend_spec``.

The module also owns the kernel layer's *trace accounting*: every jitted
kernel entry point is wrapped by ``instrumented_jit``, which bumps a
per-function counter each time JAX (re)traces the Python body. Together
with the pow2 shape-bucketing in the ops wrappers this is what the
zero-retrace regression test pins: steady-state session rounds must hit
only compiled-cache entries.
"""

from __future__ import annotations

import functools
import os
from pathlib import Path

import jax
import numpy as np

VALID_INTERPRET_SPECS = ("0", "1", "auto")

# set_interpret_override wins over the environment; the env value itself is
# parsed lazily (first kernel call, not import) and cached.
_interpret_override: str | None = None
_interpret_cached: str | None = None


def parse_interpret_spec(raw: str) -> str:
    """Validate a ``REPRO_PALLAS_INTERPRET`` value early, with a hint.

    Mirrors ``core.backend.parse_backend_spec``: malformed values fail here
    with an actionable message instead of surfacing as a deep Pallas or
    XLA error later.
    """
    if raw not in VALID_INTERPRET_SPECS:
        raise ValueError(
            f"bad REPRO_PALLAS_INTERPRET value {raw!r}; expected one of "
            f"{list(VALID_INTERPRET_SPECS)} — '0' forces compiled "
            "pallas_call kernels (real accelerators only), '1' forces "
            "Pallas interpret mode (bit-exact, slow), 'auto' (default) "
            "compiles on TPU/GPU and uses the jitted jax-numpy lowering "
            "on CPU")
    return raw


def set_interpret_override(value: str | None) -> None:
    """Programmatic override of REPRO_PALLAS_INTERPRET (None = re-read env).

    Used by tests to pin interpret mode as the kernel-semantics oracle
    against the lowered path; the value is validated like the env var.
    """
    global _interpret_override, _interpret_cached
    _interpret_override = (parse_interpret_spec(value)
                           if value is not None else None)
    _interpret_cached = None


def interpret_spec() -> str:
    """The resolved REPRO_PALLAS_INTERPRET value ('0' | '1' | 'auto')."""
    global _interpret_cached
    if _interpret_override is not None:
        return _interpret_override
    if _interpret_cached is None:
        _interpret_cached = parse_interpret_spec(
            os.environ.get("REPRO_PALLAS_INTERPRET", "auto"))
    return _interpret_cached


def kernel_mode() -> str:
    """Resolve the kernel execution mode: 'compiled' | 'interpret' | 'lowered'."""
    spec = interpret_spec()
    if spec == "1":
        return "interpret"
    if spec == "0":
        return "compiled"
    return "compiled" if jax.default_backend() in ("tpu", "gpu") \
        else "lowered"


def default_interpret() -> bool:
    """Pallas interpret flag for kernels without a lowered path.

    True unless the resolved mode is 'compiled' — i.e. unchanged behavior
    (interpret off-TPU) under 'auto', while REPRO_PALLAS_INTERPRET=0 forces
    real compilation everywhere.
    """
    return kernel_mode() != "compiled"


# Lane width of a TPU vector register: the last dim of every kernel block is
# a multiple of it (or the whole array dim).
LANES = 128


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at a fixed directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself) and nothing else is set; otherwise the cache is the repo's own
    ``.jax_cache/`` (git-ignored). A fixed path matters: the directory is
    part of the cache key, so a moving one never hits. Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def width_bucket(n: int, floor: int = 8) -> int:
    """Pow2 shape bucket with a SMALL floor for tiny widths.

    The old call sites floored padded widths at 64/128, so an 8-wide
    dictionary merge traced (and ran) a 128-lane sort network. Ship
    batches are dominated by tiny dictionary deltas, so the dedicated
    8/16/32 buckets matter: shorter unrolled compare-exchange networks
    and no cross-bucket retraces when a width crosses 64.
    """
    return max(floor, next_pow2(max(int(n), 1)))


# ---------------------------------------------------------------------------
# Buffer donation policy
# ---------------------------------------------------------------------------
#
# The fused pipelines donate their freshly-built per-call input stacks
# (donate_argnums) so XLA can reuse the buffers in place. XLA:CPU ignores
# donation and warns per call, so the donated jit variants are only
# selected in "compiled" mode — unless a test forces donation on to
# exercise the donated code path on CPU (the donated-input-reuse guard).
# NEVER route pinned snapshot or ShardedView buffers through a donated
# argument: donation invalidates the caller's copy, and pinned views are
# read again on later rounds.

_donation_override: bool | None = None


def set_donation_override(value: bool | None) -> None:
    """Force donated jit variants on/off (None = follow kernel_mode)."""
    global _donation_override
    _donation_override = value


def donation_enabled() -> bool:
    """Whether fused entry points should pick their donated jit variant."""
    if _donation_override is not None:
        return _donation_override
    return kernel_mode() == "compiled"


# ---------------------------------------------------------------------------
# Mesh-placement reduction lanes (core/backend.MeshBackend)
# ---------------------------------------------------------------------------
#
# On the mesh tier the cross-island reduction of split-accumulator partials
# runs ON the device mesh as an integer psum. Per-block int32 partials are
# each bounded by block * 0xFFFF < 2^31, but summing them across islands in
# int32 could overflow (and x64 is disabled), so every partial is psum'd as
# two 16-bit lanes: lane values stay < n_islands * 0xFFFF, exact for any
# realistic island count, and the host reassembles int64 from the lanes.

def psum_split16(partials, axis_name: str):
    """Traced: psum nonnegative int32 `partials` over `axis_name` as
    (lo, hi) 16-bit int32 lanes — exact where a direct int32 psum could
    overflow. Callers reassemble with `lanes_to_int64`."""
    lo = jax.lax.psum(partials & 0xFFFF, axis_name)
    hi = jax.lax.psum(partials >> 16, axis_name)
    return lo, hi


def lanes_to_int64(lo, hi) -> np.ndarray:
    """Host: recombine `psum_split16` lanes into exact int64 values."""
    return (np.asarray(lo).astype(np.int64)
            + (np.asarray(hi).astype(np.int64) << np.int64(16)))


# ---------------------------------------------------------------------------
# Trace accounting
# ---------------------------------------------------------------------------

_trace_counts: dict[str, int] = {}


def kernel_trace_counts() -> dict[str, int]:
    """Per-entry-point (re)trace counts since the last reset (a copy)."""
    return dict(_trace_counts)


def total_kernel_traces() -> int:
    return sum(_trace_counts.values())


def reset_kernel_trace_counts() -> None:
    _trace_counts.clear()


# Column decodes of the scans by path (``dict_ops.decode_path``): an
# aggregate column and a join build side are one decode each. Counted on
# the host at dispatch, so a compiled-cache hit counts too.
DECODE_PATHS = ("select", "hist", "gather")
_decode_counts = dict.fromkeys(DECODE_PATHS, 0)


def count_decode(path: str) -> None:
    _decode_counts[path] += 1


def decode_counts() -> dict[str, int]:
    """Column decodes per path since the last reset (a copy)."""
    return dict(_decode_counts)


def reset_decode_counts() -> None:
    _decode_counts.update(dict.fromkeys(DECODE_PATHS, 0))


def instrumented_jit(fn=None, *, static_argnames=(), donate_argnums=(),
                     name: str | None = None):
    """``jax.jit`` that counts every (re)trace of the wrapped function.

    The counter bump lives inside the traced Python body, so it executes
    exactly when JAX traces (a new shape/static-arg combination) and never
    on compiled-cache hits — which makes ``kernel_trace_counts`` a direct
    measure of recompilation. Usable as a decorator (with keywords via
    ``functools.partial``) or called directly.
    """
    if fn is None:
        return functools.partial(instrumented_jit,
                                 static_argnames=static_argnames,
                                 donate_argnums=donate_argnums, name=name)
    label = name or fn.__name__

    @functools.wraps(fn)
    def counted(*args, **kwargs):
        _trace_counts[label] = _trace_counts.get(label, 0) + 1
        return fn(*args, **kwargs)

    return jax.jit(counted, static_argnames=static_argnames,
                   donate_argnums=donate_argnums)
