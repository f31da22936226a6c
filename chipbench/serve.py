"""The served loop: one host thread drives ``HTAPSession`` as clients would.

Transactions are a closed loop: commit groups of the traffic's group size
run back to back through ``session.execute``. Queries are an open loop: they
fall due at the times of a Poisson arrival trace (``gen.arrival_times``) on
the wall clock, and before each commit group every query already due is
served as one batch, by ``session.flush_updates()`` then
``session.query_batch``. A query's latency
runs from its due time to the return of its batch; a commit group's from
the return of the previous group to the return of its own ``execute``, so
it includes any batch served in between. Queries still due when the window
closes are served once it has closed; their latency counts the wait.

Each call into a layer is a span (name, start, end on ``perf_counter``),
and JAX's compile events are kept as intervals, so the metric readers can
split time by layer. ``finish()`` is never called: it prices the modeled
cost log, which is not part of serving.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

# JAX compile stages; nested events overlap and are counted once, as a
# union of intervals.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
# Flushes of the warm-up, each of one exact shape: (columns it touches,
# writes to the widest of them). A flush or ship runs the fused dictionary
# pipeline: one program per pow2 width of the widest column's new values
# (floor 8), and one slice of its result per number of touched columns. So
# the window can meet only these: each width up to a final log's 256, and
# 6 or 7 columns where the 32 writes of one commit group miss one or two.
WARMUP_FLUSHES = tuple((8, w) for w in (8, 16, 32, 64, 128, 256)) + (
    (7, 8), (7, 16), (6, 8), (6, 16))


class Spans:
    """Host spans around each call into a layer, in ``perf_counter``
    seconds. While a profiler trace runs, each span is also a trace
    annotation, so idle gaps on the device can be labelled by it."""

    def __init__(self):
        self.spans: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"chipbench.{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)


class CompileClock:
    """JAX compile intervals (tracing, lowering, backend compile or cache
    retrieval), converted to the ``perf_counter`` clock of the spans."""

    def __init__(self):
        import jax
        self.intervals: list[tuple[float, float]] = []
        self.names: list[str] = []
        self._offset = time.perf_counter() - time.time()
        jax.monitoring.register_event_time_span_listener(self._on_span)

    def _on_span(self, event, start, end, fun_name="", **_):
        if event in COMPILE_EVENTS:
            self.intervals.append((start + self._offset, end + self._offset))
            self.names.append(fun_name)


@dataclasses.dataclass
class QueryRecord:
    index: int        # position in the window's query list
    due: float        # perf_counter time it fell due
    start: float      # its batch's start
    done: float       # its batch's return
    visible: int      # transactions executed before its batch
    answer: int
    drained: bool     # served after the window closed


@dataclasses.dataclass
class Served:
    t0: float
    t_close: float                               # the window's end
    groups: list[tuple[float, float, int]]       # (ready, done, txns)
    queries: list[QueryRecord]
    n_due: int                                   # queries due in window


class Loop:
    """One session under one cell's traffic. Every chunk of transactions it
    executes is kept, in order, for the reference to replay."""

    def __init__(self, session, to_txns, to_queries, txns, writes: bool,
                 group: int, spans: Spans):
        self.session, self.to_txns, self.to_queries = (session, to_txns,
                                                       to_queries)
        self.txns, self.writes, self.group = txns, writes, group
        self.spans = spans
        self.chunks = []    # executed so far, warm-up included
        self.executed = 0   # transactions in them

    def execute(self, n: int) -> None:
        self.run_txns(self.txns.take(n))

    def run_txns(self, chunk) -> None:
        with self.spans("execute"):
            self.session.execute(self.to_txns(chunk))
        self.chunks.append(chunk)
        self.executed += len(chunk)

    def serve(self, queries) -> tuple[float, list[int]]:
        """Flush, then answer ``queries``: (batch start, answers)."""
        t = time.perf_counter()
        if self.writes:
            with self.spans("flush"):
                self.session.flush_updates()
        with self.spans("query_batch"):
            answers = self.session.query_batch(self.to_queries(queries))
        return t, answers

    def warm_up(self, prefill_txns: int, warm_queries) -> None:
        """Bring the session to the state the window runs in, and compile
        every shape the window uses, all before the window opens.

        ``prefill_txns`` of the stream run first, in chunks of 2^14
        transactions (chunking changes no answer and no ship), which grows
        each column's dictionary into the pow2 bucket the window stays in.
        Then one flush of each shape in ``WARMUP_FLUSHES``, the widest
        column turning from flush to flush, and the warm-up queries: a
        join-free and a join query in one batch, then batches of 2 and 4
        queries of one column set (``run.warm_queries``).
        """
        with self.spans("warmup"):
            done = 0
            while done < prefill_txns:
                n = min(1 << 14, prefill_txns - done)
                self.execute(n)
                done += n
            if self.writes:
                n_cols = self.txns.n_cols
                for i, (touched, widest) in enumerate(WARMUP_FLUSHES):
                    counts = np.zeros(n_cols, dtype=np.int64)
                    cols = (i + np.arange(touched)) % n_cols
                    counts[cols] = 1
                    counts[cols[0]] = widest
                    self.run_txns(self.txns.take_writes(counts))
                    with self.spans("flush"):
                        self.session.flush_updates()
            self.serve(warm_queries[:2])
            rest = warm_queries[2:]
            for k in (2, 2, 4, 4):
                self.serve(rest[:k])
                rest = rest[k:]


def run_window(loop: Loop, queries, due: np.ndarray, seconds: float,
               tracer=None) -> Served:
    """Serve the cell's traffic for ``seconds``, then the queries still
    due. ``due`` holds the queries' due times relative to the window's
    start. ``tracer``, when given, has ``start_at`` and ``stop_at``
    (offsets into the window) and ``start()`` and ``stop()``, called
    between two operations."""
    t0 = time.perf_counter()
    t_end = t0 + seconds
    due_at = t0 + due
    groups, records = [], []
    next_q = 0
    ready = t0
    traced = tracing = False

    def record(lo, hi, start, answers, drained):
        t = time.perf_counter()
        for i, a in zip(range(lo, hi), answers):
            records.append(QueryRecord(i, float(due_at[i]), start, t,
                                       loop.executed, int(a), drained))

    while True:
        now = time.perf_counter()
        if tracer is not None:
            if not traced and now >= t0 + tracer.start_at:
                tracer.start()
                traced = tracing = True
            elif tracing and now >= t0 + tracer.stop_at:
                tracer.stop()
                tracing = False
        if now >= t_end:
            break
        k = int(np.searchsorted(due_at, now, side="right"))
        if k > next_q:
            start, answers = loop.serve(queries[next_q:k])
            record(next_q, k, start, answers, False)
            next_q = k
        if loop.writes:
            loop.execute(loop.group)
            t = time.perf_counter()
            groups.append((ready, t, loop.group))
            ready = t
        else:
            nxt = due_at[next_q] if next_q < len(due_at) else t_end
            with loop.spans("wait"):
                time.sleep(max(0.0, min(nxt, t_end) - time.perf_counter()))
    t_close = time.perf_counter()
    if tracing:
        tracer.stop()
    if next_q < len(due_at):
        start, answers = loop.serve(queries[next_q:])
        record(next_q, len(due_at), start, answers, True)
    return Served(t0=t0, t_close=t_close, groups=groups, queries=records,
                  n_due=len(due_at))
