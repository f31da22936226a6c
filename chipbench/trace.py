"""Reduce a JAX profiler trace (an XSpace) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. Device planes are
those named ``/device:<accelerator>:<n>``; on each, the ``XLA Ops`` line
holds one event per operation run (busy time is the union of their
intervals) and the ``XLA Modules`` line one event per program run (a jitted
entry point, named after its Python function). The benchmark's host spans
appear on the host plane as ``chipbench.<span>`` annotations on the same
clock; ``chipbench.traced`` marks the traced window.
"""

from __future__ import annotations

import gzip
from pathlib import Path

from chipbench.stats import union

PREFIX = "chipbench."
WINDOW = PREFIX + "traced"
# Programs whose jitted function name differs from the program's own name.
ALIASES = {"join_group_pallas": "join_group_kernel"}
TOP = 10


def program_name(module: str) -> str:
    """``jit__scan_group_kernel_body(7)`` -> ``scan_group_kernel``."""
    name = module.split("(")[0]
    if name.startswith("jit_"):
        name = name[4:]
    name = name.strip("_")
    if name.endswith("_body"):
        name = name[:-5]
    return ALIASES.get(name, name)


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def load(path_or_bytes):
    from jax.profiler import ProfileData
    if isinstance(path_or_bytes, (bytes, bytearray)):
        return ProfileData.from_serialized_xspace(bytes(path_or_bytes))
    path = Path(path_or_bytes)
    if path.suffix == ".gz":
        return ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    return ProfileData.from_file(str(path))


def _is_device(plane_name: str) -> bool:
    return (plane_name.startswith("/device:")
            and not plane_name.startswith("/device:CPU"))


def reduce(data) -> dict | None:
    """Device numbers of a trace, or None when no device ran anything.

    Returns ``window_s`` (the traced window), ``busy_s`` (union of device
    operations, averaged over the devices), ``programs`` (program name,
    seconds) for every program run, ``device_ops`` and ``idle_gaps`` (the
    ``TOP`` programs by total time and the longest idle gaps, each gap
    labelled by the innermost host span open at its middle).
    """
    pd = load(data) if not hasattr(data, "planes") else data
    spans, ops, modules = [], {}, {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
        elif _is_device(plane.name):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = [(e.start_ns, e.end_ns)
                                       for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                           for e in line.events]
    window = [(a, b) for n, a, b in spans if n == WINDOW]
    if window:
        lo, hi = window[0]
    else:
        ends = [t for ivs in ops.values() for iv in ivs for t in iv]
        if not ends:
            return None
        lo, hi = min(ends), max(ends)
    busy_by_dev = {d: union(_clip(ivs, lo, hi)) for d, ivs in ops.items()}
    busy_by_dev = {d: b for d, b in busy_by_dev.items() if b}
    if not busy_by_dev:
        return None
    busy_ns = sum(b - a for u in busy_by_dev.values() for a, b in u)
    programs, totals = [], {}
    for evs in modules.values():
        for name, a, b in evs:
            if b > lo and a < hi:
                p = program_name(name)
                programs.append((p, (b - a) / 1e9))
                totals[p] = totals.get(p, 0.0) + (b - a) / 1e9
    # idle gaps of the first device, labelled by the host span open at
    # their middle
    inner = [(n[len(PREFIX):], a, b) for n, a, b in spans if n != WINDOW]
    dev = sorted(busy_by_dev)[0]
    gaps, t = [], lo
    for a, b in busy_by_dev[dev] + [[hi, hi]]:
        if a > t:
            mid = (t + a) / 2
            open_ = [(b2 - a2, n) for n, a2, b2 in inner if a2 <= mid <= b2]
            label = min(open_)[1] if open_ else "between_spans"
            gaps.append((label, (a - t) / 1e9))
        t = max(t, b)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / len(busy_by_dev) / 1e9,
        "programs": programs,
        "device_ops": sorted(totals.items(), key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:TOP],
    }
