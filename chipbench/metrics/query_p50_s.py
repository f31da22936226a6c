"""Median latency, due time to answer, of the queries due in the window."""

from chipbench.stats import percentile, query_latencies


def read(run):
    return percentile(query_latencies(run), 50)
