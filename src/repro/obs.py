"""Spans, counters and compile records of the serving program.

Always on, in memory, for the life of the process:

- ``span(name, **attrs)``: a context manager. It opens a
  ``jax.profiler.TraceAnnotation`` of that name, so under a profiler session
  the span lands in the trace beside the device ops, on their clock. When it
  closes it writes ``Span(name, start_ns, end_ns, parent, attrs, index)``
  into a ring of the last ``RING`` spans, on ``time.perf_counter_ns``
  (``now_ns``). ``index`` numbers every span the process opened, in the
  order they opened; ``parent`` is the index of the span open around it on
  the same thread, -1 if none. Attrs are small scalars.
- ``count(name, n=1)`` and ``counters()``: process-wide counters.
- compile records: JAX's compile events, keyed by the jitted function's
  name. Each event is one ``Compile(name, phase, seconds, end_ns)`` record,
  ending at ``now_ns()`` when JAX reports it; ``phase`` is ``trace``,
  ``lower``, ``compile`` (backend compile) or ``cache_load`` (a persistent
  compile-cache hit; its seconds are taken out of that compile's). The
  counters ``compiles.<name>`` count backend compiles (cache loads
  included), ``compile_cache.requests`` / ``compile_cache.hits`` the
  persistent cache's lookups.

``snapshot()`` returns the three records; ``reset()`` clears them.
``backend_compiles()`` counts the backend compiles since the process
started, and is never reset.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, NamedTuple

import jax

RING = 65536
now_ns = time.perf_counter_ns

PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
          "/jax/core/compile/backend_compile_duration": "compile"}
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_EVENTS = {"/jax/compilation_cache/compile_requests_use_cache":
                "compile_cache.requests",
                "/jax/compilation_cache/cache_hits": "compile_cache.hits"}


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    parent: int
    attrs: dict
    index: int


class Compile(NamedTuple):
    name: str
    phase: str
    seconds: float
    end_ns: int


_lock = threading.Lock()
_local = threading.local()
_ring: list = [None] * RING
_seq = itertools.count()
_counters: Dict[str, int] = collections.defaultdict(int)
_compiles: collections.deque = collections.deque(maxlen=RING)
_backend_compiles = 0


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class span:
    """``with span("ssv.step", rows=8): ...`` — see the module docstring."""
    __slots__ = ("name", "attrs", "index", "parent", "start", "ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = _stack()
        self.index = next(_seq)
        self.parent = stack[-1] if stack else -1
        stack.append(self.index)
        self.ann = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self.ann.__enter__()
        self.start = now_ns()
        return self

    def __exit__(self, *exc):
        end = now_ns()
        self.ann.__exit__(*exc)
        stack = _stack()
        if stack and stack[-1] == self.index:
            stack.pop()
        _ring[self.index % RING] = (self.name, self.start, end, self.parent,
                                    self.attrs, self.index)
        return False


def count(name: str, n: int = 1):
    with _lock:
        _counters[name] += n


def counters() -> Dict[str, int]:
    with _lock:
        return dict(_counters)


def backend_compiles() -> int:
    return _backend_compiles


def snapshot() -> dict:
    """{"spans": the closed spans of the last RING opened, by index;
    "counters"; "compiles": compile records, oldest first}."""
    recs = sorted((r for r in list(_ring) if r is not None),
                  key=lambda r: r[5])
    # a span that outlived RING newer ones wrote over a newer span's slot
    first = recs[-1][5] - RING + 1 if recs else 0
    spans = [Span(*r) for r in recs if r[5] >= first]
    with _lock:
        return {"spans": spans, "counters": dict(_counters),
                "compiles": list(_compiles)}


def reset():
    global _ring, _seq
    with _lock:
        _ring = [None] * RING
        _seq = itertools.count()
        _counters.clear()
        _compiles.clear()
    _local.__dict__.clear()


def _program(fun_name) -> str:
    """The jitted function's name: tracing reports it bare, lowering and
    compiling as the module's ``jit(<name>)``."""
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        return name[4:-1]
    return name


def _on_duration(event, duration, fun_name=None, **_):
    if event == CACHE_LOAD_EVENT:
        # reported inside the backend compile of the program it loads,
        # which reports its own seconds (the load included) next
        _local.cache_load = getattr(_local, "cache_load", 0.0) + duration
        return
    phase = PHASES.get(event)
    if phase is None:
        return
    name = _program(fun_name)
    end = now_ns()
    recs = []
    if phase == "compile":
        load = getattr(_local, "cache_load", 0.0)
        _local.cache_load = 0.0
        if load:
            recs.append(Compile(name, "cache_load", load, end))
            duration = max(duration - load, 0.0)
        count("compiles." + name)
    recs.append(Compile(name, phase, float(duration), end))
    global _backend_compiles
    with _lock:
        _compiles.extend(recs)
        _backend_compiles += phase == "compile"


def _on_event(event, **_):
    name = CACHE_EVENTS.get(event)
    if name is not None:
        count(name)


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)
