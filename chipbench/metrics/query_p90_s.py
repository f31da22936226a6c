"""90th percentile latency, due time to answer, of the queries due in the
window, unanswered ones counting as never answered."""

from chipbench.stats import percentile, query_latencies


def read(run):
    return percentile(query_latencies(run), 90)
