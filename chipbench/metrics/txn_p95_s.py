"""95th percentile of commit-group latency over the window's groups: from
the return of the previous group to the return of this group's execute."""

from chipbench.stats import percentile


def read(run):
    return percentile([done - ready for ready, done, _ in run.served.groups],
                      95)
