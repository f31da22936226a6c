"""Seconds of JAX tracing, lowering and compiling (or compile-cache
retrieval) inside the window's ``execute`` calls."""

from chipbench.stats import overlap_s, window_spans


def read(run):
    spans = window_spans(run, "execute")
    if not spans:
        return None
    return overlap_s(run.compile_intervals, spans)
