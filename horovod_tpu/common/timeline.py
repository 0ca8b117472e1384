"""Spans and the Chrome-tracing timeline writer.

``span(name, **args)`` is the one way this program times an interval.
A span is read three ways from one pair of clock reads:

* as a ``jax.profiler.TraceAnnotation("hvd/<name>", **args)``: while a
  profiler session runs it lies in the trace's host plane, on the
  device trace's clock; with no session the annotation does nothing;
* by the metrics registry: ``hvd_span_seconds{span="hvd/<name>"}``
  holds count and seconds of every span name;
* by the Horovod Timeline file, while ``HOROVOD_TIMELINE`` /
  ``hvd.start_timeline`` has one open.

Cold spans (start-up, compile, shutdown: a few hundred a run) are also
kept, with their wall-clock start and end, in a bounded record that
``spans()`` returns.  Hot spans keep only count and seconds.

Every timestamp here is on one clock: the wall clock (the profiler's
host plane stamps its events with it too), read once at import and
advanced by ``time.perf_counter`` so that it never steps backwards.

The Timeline mirrors the reference's (reference: timeline.{h,cc}:
TimelineWriter with a dedicated writer thread fed by a lock-free SPSC
queue :48-100; per-tensor state machine NEGOTIATING → TOP_LEVEL →
ACTIVITY :106-154; written on the coordinator rank only,
operations.cc:422-425; format documented in docs/timeline.rst).  The
Python implementation uses a queue.SimpleQueue (lock-free fast path on
CPython) + daemon writer thread.  The output is standard chrome://tracing
JSON: one lane per tensor, keyed by a stable "tid" so collectives stack
per tensor name, and one lane per thread for the spans.
"""

import collections
import json
import logging
import os
import queue
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import metrics

logger = logging.getLogger("horovod_tpu.timeline")


# ---------------------------------------------------------------------------
# The clock, and spans
# ---------------------------------------------------------------------------

PREFIX = "hvd/"
COLD_SPANS_KEPT = 4096

_WALL0 = time.time()
_PERF0 = time.perf_counter()

_SPAN_SECONDS = metrics.histogram(
    "hvd_span_seconds",
    "Seconds inside each hvd/<name> span (common/timeline.py), by span "
    "name: count and sum are what the per-layer readers read")

_names: Dict[str, tuple] = {}    # short name -> (full name, label key)
# Bounded by name, so that a run that traces thousands of small programs
# cannot push its own start-up out of the record.
_cold: Dict[str, collections.deque] = collections.defaultdict(
    lambda: collections.deque(maxlen=COLD_SPANS_KEPT))
_open = threading.local()        # .stack: this thread's open cold spans
_sink: Optional["Timeline"] = None


def wall(perf_s: float) -> float:
    """The wall-clock second (since the epoch) of a
    ``time.perf_counter()`` reading."""
    return _WALL0 + (perf_s - _PERF0)


def _named(name: str) -> tuple:
    got = _names.get(name)
    if got is None:
        full = PREFIX + name
        got = _names[name] = (full, metrics.label_key(span=full))
    return got


def set_sink(timeline: Optional["Timeline"]):
    """The Timeline that finished spans are also written to (None: no
    file is open)."""
    global _sink
    _sink = timeline


def _finished(full: str, key: str, start: float, seconds: float,
              args: dict, cold: bool, parent: Optional[str] = None):
    """One finished span of ``seconds`` from the wall-clock second
    ``start``, handed to the registry, the open Timeline and (cold) the
    list."""
    _SPAN_SECONDS.observe_key(key, seconds)
    sink = _sink
    if sink is not None:
        sink.span_done(full, start * 1e6, seconds * 1e6, args)
    if cold:
        _cold[full].append({
            "name": full, "start": start, "end": start + seconds,
            "thread": threading.current_thread().name,
            "parent": parent, "args": args})


class span:
    """``with span("dispatch", op="ALLREDUCE", tensor=name) as sp:``
    times the block once.  ``sp.t0`` (a ``time.perf_counter`` reading)
    is there inside the block and ``sp.seconds`` after it, for a caller
    that feeds the same interval to something else.  ``cold=True`` also
    keeps the span, with its parent (the cold span open on this thread),
    in the list ``spans()`` returns.  The other keywords are the span's
    arguments: strings and numbers, among them the identifier that the
    spans of one exchange share (``tensor=``)."""

    __slots__ = ("name", "args", "cold", "t0", "seconds", "_key", "_ann",
                 "_kept")

    def __init__(self, name: str, cold: bool = False, **args):
        self.name, self._key = _named(name)    # "hvd/<name>" from here on
        self.args = args
        self.cold = cold
        self.seconds = 0.0
        self._kept = True

    def __enter__(self):
        if self.cold:
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            stack.append(self.name)
        self._ann = TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self.seconds = t1 - self.t0
        parent = None
        if self.cold:
            stack = _open.stack
            stack.pop()
            parent = stack[-1] if stack else None
        if self._kept:
            _finished(self.name, self._key, wall(self.t0), self.seconds,
                      self.args, self.cold, parent)
        return False

    def discard(self):
        """Leave this span out of the registry, the Timeline and the
        list: the interval turned out to hold no work (an idle poll of
        the cycle loop).  ``seconds`` is still set on exit."""
        self._kept = False


def record(name: str, start: float, end: float, **args):
    """A cold span whose interval somebody else clocked (JAX reports a
    compilation phase when it is over): ``start`` and ``end`` are
    wall-clock seconds.  No profiler annotation can be made after the
    fact; the other readers get it."""
    full, key = _named(name)
    stack = getattr(_open, "stack", None)
    _finished(full, key, start, end - start, args, True,
              stack[-1] if stack else None)


def spans() -> List[dict]:
    """The cold spans of this process in the order they ended (of each
    name the newest ``COLD_SPANS_KEPT``): ``{"name": "hvd/init/backend",
    "start", "end"`` (wall-clock seconds), ``"thread", "parent",
    "args"}``."""
    kept = [s for name in list(_cold) for s in list(_cold[name])]
    return sorted(kept, key=lambda s: s["end"])


class TimelineWriter:
    def __init__(self, file_path: str):
        self._queue: "queue.SimpleQueue" = queue.SimpleQueue()
        self._file_path = file_path
        self._active = True
        self._thread = threading.Thread(
            target=self._run, name="hvd-timeline-writer", daemon=True)
        self._thread.start()

    def enqueue(self, record: dict):
        if self._active:
            self._queue.put(record)

    def _run(self):
        try:
            os.makedirs(os.path.dirname(os.path.abspath(self._file_path)),
                        exist_ok=True)
            with open(self._file_path, "w") as f:
                f.write("[\n")
                first = True
                while True:
                    rec = self._queue.get()
                    if rec is None:
                        break
                    if not first:
                        f.write(",\n")
                    f.write(json.dumps(rec))
                    first = False
                    f.flush()
                f.write("\n]\n")
        except Exception:
            # Without this flip a writer that cannot open (or keep
            # writing) its file dies silently while enqueue() keeps
            # growing the queue unbounded for the rest of the run.
            logger.warning(
                "timeline writer failed for %s; timeline recording "
                "disabled", self._file_path, exc_info=True)
            self._active = False

    def close(self):
        if self._active:
            self._active = False
            self._queue.put(None)
            self._thread.join(timeout=5.0)


class Timeline:
    """Per-tensor span state machine emitting chrome-tracing events,
    and the writer of finished ``span``s: each on its thread's lane
    (pid 1), ``hvd/dispatch`` also as the tensor's ``XLA_<op>``
    activity and ``hvd/cycle`` as the ``CYCLE_START`` mark."""

    def __init__(self, file_path: str, rank: int = 0,
                 mark_cycles: bool = False):
        self.rank = rank
        self.mark_cycles = mark_cycles
        self.writer = TimelineWriter(file_path) if rank == 0 else None
        self._tids: Dict[tuple, int] = {}
        self._lock = threading.Lock()

    @staticmethod
    def _ts_us() -> float:
        return wall(time.perf_counter()) * 1e6

    def _lane(self, pid: int, name: str) -> int:
        """The lane of a tensor (pid 0) or of a thread (pid 1)."""
        with self._lock:
            tid = self._tids.get((pid, name))
            if tid is None:
                tid = self._tids[(pid, name)] = len(self._tids) + 1
                if self.writer:
                    self.writer.enqueue({
                        "name": "thread_name", "ph": "M", "pid": pid,
                        "tid": tid, "args": {"name": name}})
            return tid

    def _tid(self, tensor_name: str) -> int:
        return self._lane(0, tensor_name)

    def negotiate_start(self, tensor_name: str, request_type: str):
        self._emit_begin(tensor_name, f"NEGOTIATE_{request_type}",
                         self._ts_us())

    def negotiate_rank_ready(self, tensor_name: str, rank: int):
        if self.writer:
            self.writer.enqueue({
                "name": str(rank), "ph": "i", "pid": 0,
                "tid": self._tid(tensor_name), "ts": self._ts_us(),
                "s": "t"})

    def negotiate_end(self, tensor_name: str):
        self._emit_end(tensor_name, self._ts_us())

    def span_done(self, name: str, start_us: float, dur_us: float,
                  args: dict):
        """A finished ``span``, on the lane of the thread that ran it."""
        if not self.writer:
            return
        self.writer.enqueue({
            "name": name, "ph": "X", "pid": 1,
            "tid": self._lane(1, threading.current_thread().name),
            "ts": start_us, "dur": dur_us, "args": args})
        if name == PREFIX + "dispatch":
            self._emit_begin(args["tensor"], "XLA_" + args["op"], start_us)
            self._emit_end(args["tensor"], start_us + dur_us)
        elif name == PREFIX + "cycle" and self.mark_cycles:
            self.writer.enqueue({
                "name": "CYCLE_START", "ph": "i", "pid": 0, "tid": 0,
                "ts": start_us, "s": "g"})

    def instant(self, name: str):
        """Process-scoped instant event (steady-state replay
        enter/exit marks and similar one-shot state flips)."""
        if self.writer:
            self.writer.enqueue({
                "name": name, "ph": "i", "pid": 0, "tid": 0,
                "ts": self._ts_us(), "s": "p"})

    def _emit_begin(self, tensor_name: str, name: str, ts_us: float):
        if self.writer:
            self.writer.enqueue({
                "name": name, "ph": "B", "pid": 0,
                "tid": self._tid(tensor_name), "ts": ts_us})

    def _emit_end(self, tensor_name: str, ts_us: float):
        if self.writer:
            self.writer.enqueue({
                "ph": "E", "pid": 0, "tid": self._tid(tensor_name),
                "ts": ts_us})

    def close(self):
        if self.writer:
            self.writer.close()
            self.writer = None
