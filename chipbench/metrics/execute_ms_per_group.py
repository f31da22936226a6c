"""Mean ``session.execute`` time of a commit group in the window
(transactional island, with the capacity-triggered ships it runs)."""

from chipbench.stats import window_spans


def read(run):
    spans = window_spans(run, "execute")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
