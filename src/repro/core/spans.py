"""Host spans of the query path: one recorder for every layer.

``span(name, qid=None, **attrs)`` is a context manager around a call
into a layer. Each span records its name, its id, its parent's id, the
query id (inherited from the parent unless given), its start and end on
``time.perf_counter_ns()``, and the attributes known when it opens. The
body may set the query id once it is known (``sp.qid = ...``); spans
opened after that inherit it. A span whose body raises is closed and
recorded all the same.

Each span also opens a ``jax.profiler.TraceAnnotation`` of the same
name, carrying its query id and attributes, so in a profiled run the
spans sit on the profiler's host clock beside the device events and the
trace viewer shows their arguments. With the profiler off a span costs
a few microseconds.

The parent stack is per thread: a span opened on a pool thread is a
root there. Closed spans go into ``RING``, a bounded in-memory ring;
``RING.snapshot()`` returns its records with the count of records the
ring has pushed out. Every name starts with ``engine.``:

    engine.serve       QueryServer.serve, the whole call (root)   requests
    engine.plan        one request's lowering, plan-cache lookup,
                       validation and stage compilation           (qid)
    engine.schedule    the scheduler running the stages' fragments
    engine.fragment    one fragment on a worker   qid, pipeline, fragment,
                                                  attempt
    engine.fragment.assemble  the copy joining a fragment's decoded
                       objects into one batch
    engine.store.get   an object read / write     tier; bytes on put
    engine.store.put   (ObjectStore, KVStore)
    engine.codec.decode  columnar deserialization            bytes
    engine.codec.encode  columnar serialization              rows
    engine.ops         the fused backend's drivers (host glue)
    engine.udf         one UDF call, on either backend        udf, rows_in,
                                                              rows_out
    engine.device      a jitted call or kernel and the copy   kernel,
                       of its outputs to the host             h2d_bytes
    engine.finalize    the coordinator's merge of a query's results

A layer's time is the self time of its spans: their duration less
what their child spans cover.

``count(name, n)`` adds to a program counter: to the process total in
``COUNTERS``, which a caller reads before and after a stretch of work,
and to the attribute ``name`` of the innermost open span, so that a
reader of the ring can sum it over the spans of a window. The counters:

    exchange_bytes            shuffle frame bytes a fragment puts
    session_window.calls      calls of the traced session window
    session_window.rows       rows they were handed (padding left out)
    session_window.purchases  qualifying purchases they found
"""
from __future__ import annotations

import collections
import functools
import itertools
import threading
import time
from typing import Callable, Optional

from jax.profiler import TraceAnnotation

# Enough for several thousand queries' spans; a reader that needs a
# window's records checks ``dropped`` before trusting them.
CAPACITY = 1 << 16


class SpanRing:
    """Bounded ring of closed spans, safe to append from any thread."""

    def __init__(self, capacity: int = CAPACITY):
        self._records: collections.deque = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self.dropped = 0

    def append(self, record: "Span") -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self.dropped += 1
            self._records.append(record)

    def snapshot(self) -> tuple[list["Span"], int]:
        """The records, oldest closed first, and how many the ring has
        pushed out so far."""
        with self._lock:
            return list(self._records), self.dropped


RING = SpanRing()

COUNTERS: collections.Counter = collections.Counter()
_counters_lock = threading.Lock()

_ids = itertools.count(1)


class _Stack(threading.local):
    def __init__(self):
        self.open: list[Span] = []


_stack = _Stack()


class Span:
    """One span; the context manager that ``span()`` returns."""

    __slots__ = ("name", "id", "parent", "qid", "start_ns", "end_ns",
                 "attrs", "_annotation")

    def __init__(self, name: str, qid: Optional[str], attrs: dict):
        self.name = name
        self.qid = qid
        self.attrs = attrs
        self.parent: Optional[int] = None
        self.start_ns = self.end_ns = 0
        self.id = 0

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    def __enter__(self) -> "Span":
        stack = _stack.open
        if stack:
            up = stack[-1]
            self.parent = up.id
            if self.qid is None:
                self.qid = up.qid
        self.id = next(_ids)
        stack.append(self)
        if self.qid is None:
            self._annotation = TraceAnnotation(self.name, **self.attrs)
        else:
            self._annotation = TraceAnnotation(self.name, qid=self.qid,
                                               **self.attrs)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.end_ns = time.perf_counter_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        _stack.open.pop()
        RING.append(self)
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to the program counter ``name`` and to the attribute of
    the same name of this thread's innermost open span."""
    with _counters_lock:
        COUNTERS[name] += n
    stack = _stack.open
    if stack:
        attrs = stack[-1].attrs
        attrs[name] = attrs.get(name, 0) + n


def span(name: str, qid: Optional[str] = None, **attrs) -> Span:
    """A span around a call into a layer: ``with span("engine.x") as sp:``.
    ``qid`` names the query the work belongs to; by default the
    enclosing span's."""
    return Span(name, qid, attrs)


def spanned(name: str, attrs: Optional[Callable[..., dict]] = None):
    """Decorator: each call of the function runs inside ``span(name)``.
    ``attrs``, if given, is called with the call's arguments and returns
    the span's attributes."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with Span(name, None,
                      {} if attrs is None else attrs(*args, **kwargs)):
                return fn(*args, **kwargs)
        return call
    return wrap
