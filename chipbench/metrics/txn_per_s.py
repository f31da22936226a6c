"""Transactions committed in the window per second of the window: every
commit group that returned, over the window's whole length."""


def read(run):
    s = run.served
    if not s.groups:
        return None
    return sum(n for _, _, n in s.groups) / (s.t_close - s.t0)
