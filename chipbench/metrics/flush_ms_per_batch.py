"""Mean ``session.flush_updates`` time of a query batch in the window
(propagation: shipping, application, snapshot swap)."""

from chipbench.stats import window_spans


def read(run):
    spans = window_spans(run, "flush")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
