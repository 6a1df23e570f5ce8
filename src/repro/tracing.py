"""Spans and counters of the program's layers, on the profiler's clock.

One recorder for the whole process:

- :func:`span` times a block of work.  Its record goes to a bounded
  in-memory ring, always; where JAX is loaded and a profiler session is
  recording (``jax.profiler.start_trace``, ``start_server``), the block is
  also a host ``TraceMe`` named ``pd:<name>``, so it lies beside the
  device's operations in the same trace.
- :func:`record` adds an interval measured elsewhere, such as one that
  crosses threads, to the ring only.
- :func:`count` adds to a named counter.

A record's parent is the innermost span open on the thread that made it,
and its ``cu`` the request it serves: given explicitly, or that of its
parent.  While JAX is loaded, its tracing, lowering, compiling and
persistent-cache loads become ``jit.trace``, ``jit.lower``,
``jit.compile`` and ``jit.cache_load`` records under the span open on
the compiling thread, and counters of their seconds (``jit.compile``
covers a persistent-cache load too).

This module imports no JAX: the runtime's core must stay importable before
a launcher sets its XLA flags.
"""

from __future__ import annotations

import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional

#: prefix of the program's spans in a profiler trace
PREFIX = "pd:"
#: records the ring keeps; older ones are dropped and counted
#: (``tracing.dropped``)
CAPACITY = 65536

#: jax.monitoring duration events → record and counter names
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jit.cache_load",
}


class Record(NamedTuple):
    name: str
    start: float  # time.monotonic()
    end: float
    id: int
    parent: Optional[int]  # id of the enclosing span on the same thread
    cu: Optional[str]  # the request (CU id) the work served
    attrs: Dict[str, Any]


_lock = threading.Lock()
_ring: "deque[Record]" = deque(maxlen=CAPACITY)
_counters: Dict[str, float] = {}
_ids = itertools.count(1)
_local = threading.local()
_listening = False


def _stack() -> List["span"]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _append(rec: Record) -> None:
    with _lock:
        if len(_ring) == CAPACITY:
            _counters["tracing.dropped"] = _counters.get("tracing.dropped", 0) + 1
        _ring.append(rec)


def _on_duration(event: str, secs: float, **kwargs) -> None:
    name = JIT_EVENTS.get(event)
    if name is None:
        return
    end = time.monotonic()
    fun = kwargs.get("fun_name")
    attrs = {"fun_name": fun} if fun is not None else {}
    record(name, end - secs, end, **attrs)
    count(name, secs)


def _listen() -> None:
    """Install the compile listener, once, when JAX is loaded."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)


class span:
    """``with span(name, cu=None, **attrs) as sp``: time the block.

    ``sp.attrs`` may be added to inside the block; ``sp.start`` is set on
    entry and ``sp.end`` on exit, both ``time.monotonic()``."""

    __slots__ = ("name", "cu", "attrs", "id", "parent", "start", "end", "_me")

    def __init__(self, name: str, cu: Optional[str] = None, **attrs: Any):
        self.name = name
        self.cu = cu
        self.attrs = attrs
        self._me = None

    def __enter__(self) -> "span":
        stack = _stack()
        outer = stack[-1] if stack else None
        self.parent = outer.id if outer is not None else None
        if self.cu is None and outer is not None:
            self.cu = outer.cu
        self.id = next(_ids)
        profiler = sys.modules.get("jax.profiler")
        if profiler is not None:
            if not _listening:
                _listen()
            if profiler.TraceAnnotation.is_enabled():
                meta = dict(self.attrs)
                if self.cu is not None:
                    meta["cu"] = self.cu
                self._me = profiler.TraceAnnotation(PREFIX + self.name, **meta)
                self._me.__enter__()
        stack.append(self)
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.monotonic()
        _stack().pop()
        if self._me is not None:
            self._me.__exit__(*exc)
            self._me = None
        _append(
            Record(
                self.name, self.start, self.end, self.id, self.parent, self.cu,
                self.attrs,
            )
        )


def record(
    name: str, start: float, end: float, cu: Optional[str] = None, **attrs: Any
) -> None:
    """Add an interval timed elsewhere (``time.monotonic()`` bounds) under
    the span open on this thread."""
    stack = _stack()
    outer = stack[-1] if stack else None
    if cu is None and outer is not None:
        cu = outer.cu
    parent = outer.id if outer is not None else None
    _append(Record(name, start, end, next(_ids), parent, cu, attrs))


def count(name: str, n: float = 1) -> None:
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> Dict[str, float]:
    """A snapshot of every counter."""
    with _lock:
        return dict(_counters)


def spans() -> List[Record]:
    """A snapshot of the ring, oldest record first."""
    with _lock:
        return list(_ring)
