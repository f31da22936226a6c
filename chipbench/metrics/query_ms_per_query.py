"""``session.query_batch`` time in the window per query answered there
(analytics after the flush: engine, backend, kernels)."""

from chipbench.stats import window_spans


def read(run):
    n = sum(1 for q in run.served.queries if not q.drained)
    if not n:
        return None
    return 1e3 * sum(b - a for a, b in window_spans(run, "query_batch")) / n
