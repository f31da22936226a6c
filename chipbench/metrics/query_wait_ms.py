"""Mean wait of a due query before its batch starts (served loop)."""


def read(run):
    qs = run.served.queries
    if not qs:
        return None
    return 1e3 * sum(q.start - q.due for q in qs) / len(qs)
