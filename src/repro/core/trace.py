"""Program spans and device scopes on the profiler's clock.

While a profiler session captures, a host span opens a
``jax.profiler.TraceAnnotation`` (a ``TraceMe``), so it lands in the
profiler's ``.xplane.pb`` on the same clock as the device ops, and appends
a :class:`Record` to a bounded in-process buffer that :func:`records`
reads: its name, start and end on ``time.perf_counter_ns``, the enclosing
span's index, a request id and the jaxpr traces and backend compiles that
ran inside it on its thread (its children's included).  With no session a
span costs one ``is_enabled()`` call.  The profiler is the only switch:
there is no option, variable or exporter.

Capture and read::

    with jax.profiler.trace("/tmp/prof"):
        mr.run(items)
    for r in trace.records():
        print(r.name, (r.end_ns - r.start_ns) / 1e6, "ms", r.rid)

Host spans, by entry point (children indented):

- ``mr.run`` / ``mr.run_distributed`` (``rid``: process-wide job number)
    - ``mr.dispatch``: the executable call
    - ``mr.sync``: the host blocking on a device value (shuffle overflow)
    - ``mr.post``: range densify and result assembly
- ``mr.ingest`` (``rid``: the batch id it publishes)
    - ``mr.seed``: a window slot re-seeded at a new slide period
    - ``mr.dispatch``: the ingest executable call
- ``mr.snapshot`` (``rid``: the batch id it reads)
    - ``mr.merge``: slot tables and their merge
    - ``mr.finalize``: finalize of one slot's state

Device scopes (``jax.named_scope``; they set ``op_name`` metadata and
never change the computation): ``mr.map``, ``mr.premap`` (the derived
premap, map-side), ``mr.fold``, ``mr.partition``,
``mr.segment_reduce``, ``mr.shuffle``, ``mr.merge``, ``mr.finalize``.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple

import jax

# host spans
RUN = "mr.run"
RUN_DISTRIBUTED = "mr.run_distributed"
DISPATCH = "mr.dispatch"
SYNC = "mr.sync"
POST = "mr.post"
INGEST = "mr.ingest"
SEED = "mr.seed"
SNAPSHOT = "mr.snapshot"
# device scopes (MERGE and FINALIZE are host spans in the service too)
MAP = "mr.map"
PREMAP = "mr.premap"
FOLD = "mr.fold"
PARTITION = "mr.partition"
SEGMENT_REDUCE = "mr.segment_reduce"
SHUFFLE = "mr.shuffle"
MERGE = "mr.merge"
FINALIZE = "mr.finalize"

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

BUFFER_RECORDS = 65536


class Record(NamedTuple):
    """One finished span of a profiled stretch.  ``index`` numbers spans
    in the order they opened; ``parent`` is the enclosing span's index,
    or -1 at the top of its thread."""

    index: int
    name: str
    start_ns: int
    end_ns: int
    parent: int
    rid: int | None
    traces: int
    compiles: int


_annotation = jax.profiler.TraceAnnotation
_enabled = jax.profiler.TraceAnnotation.is_enabled  # profiler capturing
_buffer: collections.deque = collections.deque(maxlen=BUFFER_RECORDS)
_buffer_lock = threading.Lock()
_local = threading.local()  # .stack: this thread's open recording spans
_span_index = itertools.count()
_job_number = itertools.count(1)


def next_job() -> int:
    """A new process-wide job number, the ``rid`` of an entry-point run."""
    return next(_job_number)


def records() -> list[Record]:
    """The spans recorded so far, oldest first (the newest
    ``BUFFER_RECORDS`` of them)."""
    with _buffer_lock:
        return list(_buffer)


def clear() -> None:
    with _buffer_lock:
        _buffer.clear()


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


class span:
    """``with span(name, rid=None) as s:`` a host span; ``s.rid`` may be
    set inside the block.  A span that opens inside a recording span with
    no ``rid`` of its own takes the enclosing span's."""

    __slots__ = ("name", "rid", "_ann", "_index", "_parent", "_start",
                 "_traces", "_compiles")

    def __init__(self, name: str, rid: int | None = None):
        self.name = name
        self.rid = rid
        self._index = None  # set while recording

    def __enter__(self):
        # a TraceMe opened with no session records nothing, so the span
        # makes one only while the profiler captures
        if _enabled():
            self._ann = _annotation(self.name)
            self._ann.__enter__()
            stack = _stack()
            if stack:
                outer = stack[-1]
                self._parent = outer._index
                if self.rid is None:
                    self.rid = outer.rid
            else:
                self._parent = -1
            self._index = next(_span_index)
            self._traces = self._compiles = 0
            stack.append(self)
            self._start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self._index is not None:
            end = time.perf_counter_ns()
            stack = _local.stack
            stack.pop()
            if stack:
                stack[-1]._traces += self._traces
                stack[-1]._compiles += self._compiles
            if self.rid is not None:
                self._ann.set_metadata(rid=self.rid)
            rec = Record(self._index, self.name, self._start, end,
                         self._parent, self.rid, self._traces, self._compiles)
            with _buffer_lock:
                _buffer.append(rec)
            self._ann.__exit__(*exc)
        return False


def _count(event: str, duration_secs: float, **_) -> None:
    """Duration listener: a trace or compile on this thread counts toward
    its innermost recording span."""
    stack = getattr(_local, "stack", None)
    if not stack:
        return
    if event == TRACE_EVENT:
        stack[-1]._traces += 1
    elif event == COMPILE_EVENT:
        stack[-1]._compiles += 1


jax.monitoring.register_event_duration_secs_listener(_count)
