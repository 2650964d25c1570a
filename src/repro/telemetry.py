"""Spans and counters of the process, on the profiler's clock.

The serving path records its stages here; the kernel layer counts here
which layer-0 form each wavefront kernel trace took.

``span(name)`` brackets one stage of host work.  Under a profiler session
it enters a ``jax.profiler.TraceAnnotation`` of the same name, so the
stage lies on a host line of the trace, on the same clock as the device's
operations.  Always, it records the stage's duration and its *self time*
(the duration less the time of the spans opened inside it on the same
thread) into per-name ``LatencyHistogram``s of ~1.1% bins.
``record(name, seconds)`` adds a duration that no ``with`` block can
bracket, such as a chunk's wait in the arrival queue, which starts on the
producer's thread and ends on the scheduler's.  ``count(name, n)`` is a
counter.  ``snapshot()`` reads it all as plain numbers; ``reset()``
starts over.

One recorder serves the process, as the profiler does.  Recording is
always on and costs about a microsecond a span on a TPU host with no
profiler session: two clock reads and two list appends on a span object
that each thread keeps per name, binned into the histograms a thousand at
a time.  Each thread records into tables of its own, so the serving path
takes no lock; ``snapshot`` adds the tables up.

    with telemetry.span("engine.finish"):
        ...
    telemetry.snapshot()["spans"]["engine.finish"]["p50_us"]
"""

from __future__ import annotations

import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

from repro.latency import LatencyHistogram

__all__ = ["Recorder", "count", "record", "reset", "snapshot", "span"]

#: durations a ``_Stat`` holds unbinned
_BIN_EVERY = 1024
_now = time.perf_counter_ns
_profiling = TraceAnnotation.is_enabled


class _Stat:
    """One name's records on one thread: histograms of durations and of
    self times (us), and the nanoseconds not yet binned into them."""

    __slots__ = ("total", "own", "durs", "owns")

    def __init__(self):
        self.clear()

    def clear(self) -> None:
        self.total = LatencyHistogram()
        self.own = LatencyHistogram()
        self.durs: list[int] = []
        self.owns: list[int] = []

    def bin(self) -> None:
        durs, owns, self.durs, self.owns = self.durs, self.owns, [], []
        if durs:
            self.total.record_many(np.asarray(durs) * 1e-3)
            self.own.record_many(np.asarray(owns) * 1e-3)

    def fold_into(self, other: "_Stat") -> None:
        other.total.merge(self.total)
        other.own.merge(self.own)
        other.durs.extend(self.durs)
        other.owns.extend(self.owns)
        if len(other.durs) >= _BIN_EVERY:
            other.bin()


class _Table:
    """One thread's records: a ``_Stat`` per name, the counters, a reusable
    ``_Span`` per name, and the innermost open span."""

    __slots__ = ("thread", "stats", "counts", "spans", "open")

    def __init__(self, thread):
        self.thread = thread
        self.stats: dict[str, _Stat] = {}
        self.counts: dict[str, int] = {}
        self.spans: dict[str, _Span] = {}
        self.open: _Span | None = None

    def stat(self, name: str) -> _Stat:
        stat = self.stats.get(name)
        if stat is None:
            stat = self.stats[name] = _Stat()
        return stat

    def fold(self, other: "_Table") -> None:
        for name, stat in list(other.stats.items()):
            stat.fold_into(self.stat(name))
        for name, n in list(other.counts.items()):
            self.counts[name] = self.counts.get(name, 0) + n


class _Span:
    """The span ``name`` on one thread.  One object serves every span of
    the name on the thread that does not open inside another of the same
    name, so opening a span makes no object."""

    __slots__ = ("_table", "_name", "_stat", "_ann", "_parent", "_t0",
                 "_child_ns", "_ns", "is_open")

    def __init__(self, table: _Table, name: str):
        self._table = table
        self._name = name
        self._stat = table.stat(name)
        self._ns = 0
        self.is_open = False

    @property
    def seconds(self) -> float:
        """The duration of the span's last closing."""
        return self._ns * 1e-9

    def __enter__(self) -> "_Span":
        # an annotation made with no profiler session records nothing, so
        # none is made: asking costs a fifth of making one
        if _profiling():
            self._ann = TraceAnnotation(self._name)
            self._ann.__enter__()
        else:
            self._ann = None
        self.is_open = True
        table = self._table
        self._parent, table.open = table.open, self
        self._child_ns = 0
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # no Python call on the common path below: a profiler session's
        # Python tracer makes every one costly
        dur = self._ns = _now() - self._t0
        parent = self._table.open = self._parent
        if parent is not None:
            parent._child_ns += dur
        stat = self._stat
        stat.durs.append(dur)
        stat.owns.append(dur - self._child_ns)
        if len(stat.durs) >= _BIN_EVERY:
            stat.bin()  # vectorized, a thousand at a time
        self.is_open = False
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)


class Recorder:
    """Spans and counters from every thread of the process."""

    def __init__(self):
        self._lock = threading.Lock()
        self._local = threading.local()
        self._tables: list[_Table] = []
        # what threads that have exited recorded, folded into one table
        self._retired = _Table(None)

    def _register(self) -> _Table:
        """Make this thread's table, folding the tables of threads that
        have exited into one."""
        table = self._local.table = _Table(threading.current_thread())
        with self._lock:
            live = []
            for t in self._tables:
                if t.thread.is_alive():
                    live.append(t)
                else:
                    self._retired.fold(t)
            self._tables = live + [table]
        return table

    def span(self, name: str) -> _Span:
        """Context manager: one stage of host work named ``name``; its
        ``seconds`` holds the duration once the block has closed, until
        the next span of the name on the thread closes."""
        try:
            table = self._local.table
        except AttributeError:
            table = self._register()
        sp = table.spans.get(name)
        if sp is None:
            sp = table.spans[name] = _Span(table, name)
        elif sp.is_open:  # inside a span of its own name
            sp = _Span(table, name)
        return sp

    def record(self, name: str, seconds: float) -> None:
        """One duration of ``name``, measured by the caller (its self time
        is all of it)."""
        try:
            table = self._local.table
        except AttributeError:
            table = self._register()
        ns = int(seconds * 1e9)
        stat = table.stats.get(name)
        if stat is None:
            stat = table.stats[name] = _Stat()
        stat.durs.append(ns)
        stat.owns.append(ns)
        if len(stat.durs) >= _BIN_EVERY:
            stat.bin()

    def count(self, name: str, n: int = 1) -> None:
        try:
            table = self._local.table
        except AttributeError:
            table = self._register()
        table.counts[name] = table.counts.get(name, 0) + n

    def snapshot(self) -> dict:
        """``{"spans": {name: {count, total_s, p50_us, p99_us, max_us,
        self_p50_us}}, "counters": {name: n}}`` over every thread."""
        whole = _Table(None)
        with self._lock:
            for t in [self._retired, *self._tables]:
                whole.fold(t)
        spans = {}
        for name, stat in sorted(whole.stats.items()):
            stat.bin()
            total, own = stat.total, stat.own
            if not total.count:
                continue
            spans[name] = {
                "count": total.count,
                "total_s": total.sum_us * 1e-6,
                "p50_us": total.percentile(50),
                "p99_us": total.percentile(99),
                "max_us": total.max_us,
                "self_p50_us": own.percentile(50),
            }
        return {"spans": spans, "counters": dict(sorted(whole.counts.items()))}

    def reset(self) -> None:
        """Forget every record and count (spans open now still record when
        they close)."""
        with self._lock:
            self._retired = _Table(None)
            for t in self._tables:
                for stat in list(t.stats.values()):
                    stat.clear()
                t.counts.clear()


#: the process's recorder, and its methods as module functions
_recorder = Recorder()
span = _recorder.span
record = _recorder.record
count = _recorder.count
snapshot = _recorder.snapshot
reset = _recorder.reset
