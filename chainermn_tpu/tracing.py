"""Spans inside the program, on the profiler's clock.

The spans of the hot path (:func:`span`: a scheduler iteration, a trainer
step) are on exactly while a ``jax.profiler`` session is active in the
process (``jax.profiler.start_trace``, the ``Profile`` trainer extension,
the benchmark's ``--trace 1``) and have no other switch. On, a span is a
``jax.profiler.TraceAnnotation`` (an event on ``/host:CPU`` of the same
xplane as the device lines) AND a row ``(id, parent_id, name, t0, t1,
attrs)`` on ``time.perf_counter`` in a bounded in-memory deque. Off,
:func:`span` returns one shared no-op object: no clock read, no allocation.

Two records are kept with or without a session, because their work happens a
bounded number of times a process and mostly before any session starts:

* the compile log (:func:`compiles`): one :class:`CompileRow` for every
  program JAX lowers and compiles, from a ``jax.monitoring`` listener
  registered when this module is imported;
* lifecycle spans (:func:`lifecycle_span`, :func:`lifecycle_rows`):
  ``engine.build``, ``step.build`` and one ``program.first_call`` for the
  first dispatch of each compiled program key, in a deque of their own.

:func:`compile_table` joins the two: which program key compiled what, from
the cache or not, and how long its executable's first run took.

Nothing is exported or written here; medians and ratios are the readers'
work (``benchmark/metrics/``, docs/serving.md).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

import jax.monitoring
from jax.profiler import TraceAnnotation

__all__ = ["MAX_COMPILE_ROWS", "MAX_LIFECYCLE_ROWS", "MAX_ROWS", "OFF",
           "CompileRow", "Row", "clear", "compile_table", "compiles",
           "lifecycle_rows", "lifecycle_span", "rows", "span"]

#: rows kept; the oldest fall off (about ten rows a scheduler iteration)
MAX_ROWS = 1 << 16
#: lifecycle rows kept, apart from the iteration rows, which cannot push
#: them out
MAX_LIFECYCLE_ROWS = 1 << 10
#: compile rows kept
MAX_COMPILE_ROWS = 1 << 12


class Row(NamedTuple):
    id: int
    parent_id: Optional[int]     # innermost span open on the thread
    name: str
    t0: float                    # time.perf_counter seconds
    t1: float
    attrs: dict


class CompileRow(NamedTuple):
    """One program JAX lowered and compiled (or fetched from its persistent
    cache), as its ``jax.monitoring`` events told it."""
    fun_name: str       # the lowered module's name, e.g. "jit(_decode_k)"
    t_end: float        # time.perf_counter when the executable was there
    trace_s: float      # the function to a jaxpr (inner jits' lie inside)
    lower_s: float      # the jaxpr to an MLIR module
    backend_s: float    # XLA's compile, or the cache's lookup and load
    cache: str          # "hit" | "miss" (compiled, entry written) | "none"
    #                     (no cache directory, or a compile its thresholds
    #                     keep out: never written, so never a hit)
    retrieval_s: float  # of backend_s, the cache's read; 0.0 unless a hit


_rows: collections.deque = collections.deque(maxlen=MAX_ROWS)
_lifecycle: collections.deque = collections.deque(maxlen=MAX_LIFECYCLE_ROWS)
_compiles: collections.deque = collections.deque(maxlen=MAX_COMPILE_ROWS)
_ids = itertools.count(1)
_open = threading.local()        # .stack: ids of this thread's open spans


class _Off:
    """What :func:`span` returns outside a profiler session. Falsy, so
    ``if sp:`` guards attributes that cost something to compute."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs):
        pass


OFF = _Off()


class _Span:
    __slots__ = ("id", "parent_id", "name", "attrs", "t0", "_ann", "_into")

    def __init__(self, name, attrs, into):
        self.name, self.attrs, self._into = name, attrs, into

    def __enter__(self):
        stack = _open.__dict__.setdefault("stack", [])
        self.id, self.parent_id = next(_ids), (stack[-1] if stack else None)
        stack.append(self.id)
        self._ann = TraceAnnotation(self.name, **self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _open.stack.pop()
        self._into.append(Row(self.id, self.parent_id, self.name, self.t0,
                              t1, self.attrs))
        return False

    def set(self, **attrs):
        """Counts known only at the end (they reach the row, not the
        annotation, which was written at entry)."""
        self.attrs.update(attrs)


def span(name: str, /, **attrs):
    """Context manager around one piece of work; the object it yields has
    ``.set(**attrs)``. Spans of one request carry ``request=<id>``."""
    if not TraceAnnotation.is_enabled():
        return OFF
    return _Span(name, attrs, _rows)


def lifecycle_span(name: str, /, **attrs):
    """:func:`span` for work done a bounded number of times a process (an
    engine built, a program's first dispatch): recorded with or without a
    profiler session, into :func:`lifecycle_rows` and never into
    :func:`rows`; also an annotation when a session happens to be on. NOT
    for anything an iteration repeats."""
    return _Span(name, attrs, _lifecycle)


def _whole_inside(recorded, lo, hi):
    return [r for r in recorded if (lo is None or r.t0 >= lo)
            and (hi is None or r.t1 <= hi)]


def rows(lo: Optional[float] = None, hi: Optional[float] = None):
    """The recorded rows that lie whole inside ``[lo, hi]``
    (``time.perf_counter`` seconds), oldest first."""
    return _whole_inside(_rows, lo, hi)


def lifecycle_rows(lo: Optional[float] = None, hi: Optional[float] = None):
    """The lifecycle rows that lie whole inside ``[lo, hi]``, oldest
    first."""
    return _whole_inside(_lifecycle, lo, hi)


def clear() -> None:
    """Drop the iteration rows. The lifecycle rows and the compile log tell
    how the process started, and stay."""
    _rows.clear()


# -- the compile log -----------------------------------------------------
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE = {"/jax/compilation_cache/cache_hits": "hit",
          "/jax/compilation_cache/cache_misses": "miss"}

_compiling = threading.local()   # the program this thread is compiling


def _on_duration(event, duration, fun_name="", **kw):
    now = _compiling.__dict__
    if event == _TRACE:
        # an inner jit's trace ends inside its outer's and before it: the
        # lowering below picks its own function's by name
        now.setdefault("traces", {})[fun_name] = duration
    elif event == _LOWER:
        traced = now.pop("traces", {})
        inner = fun_name[fun_name.find("(") + 1:-1]   # "jit(f)" -> "f"
        now.update(trace_s=traced.get(inner, traced.get(fun_name, 0.0)),
                   lower_s=duration, cache="none", retrieval_s=0.0)
    elif event == _RETRIEVAL:
        now["retrieval_s"] = duration
    elif event == _BACKEND:
        _compiles.append(CompileRow(
            fun_name, time.perf_counter(), now.pop("trace_s", 0.0),
            now.pop("lower_s", 0.0), duration, now.pop("cache", "none"),
            now.pop("retrieval_s", 0.0)))


def _on_event(event, **kw):
    if event in _CACHE:
        _compiling.cache = _CACHE[event]


def _listen():
    """Register the two listeners unless this module's are there already (a
    reload keeps the functions' globals, so the old ones feed the new
    deque). JAX calls them only while it lowers or compiles a program; no
    backend is touched."""
    from jax._src import monitoring

    def mine(listeners, fn):
        return any(getattr(cb, "__module__", None) == __name__
                   and getattr(cb, "__name__", None) == fn.__name__
                   for cb in listeners)

    if not mine(monitoring.get_event_duration_listeners(), _on_duration):
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if not mine(monitoring.get_event_listeners(), _on_event):
        jax.monitoring.register_event_listener(_on_event)


_listen()


def compiles(lo: Optional[float] = None, hi: Optional[float] = None):
    """The compile rows whose ``t_end`` lies inside ``[lo, hi]``, oldest
    first: every program JAX compiled in this process since this module was
    imported (the newest ``MAX_COMPILE_ROWS``)."""
    return [c for c in _compiles if (lo is None or c.t_end >= lo)
            and (hi is None or c.t_end <= hi)]


def compile_table(lo: Optional[float] = None, hi: Optional[float] = None):
    """What a start cost, program by program, as plain data: first one dict
    for every ``program.first_call`` span whole inside ``[lo, hi]``, then
    one for every other compile row that ended there, each group oldest
    first.

    A compile row belongs to the ``program.first_call`` span its ``t_end``
    lies in; the span's dict sums the durations of its rows (``compiles``
    of them) under the name and the cache verdict of the costliest, and its
    ``first_run_s`` is the span less those durations: the executable's
    first run, blocked on. Two dicts with one ``program`` and ``key`` are a
    program that was built twice. Every other row gives a dict of its own
    with ``program`` and ``key`` ``None``, ``first_run_s`` ``None`` and, as
    ``span``, the ``engine.build`` or ``step.build`` it ended inside or
    ``None``: a compile outside the program's spans (the caller's own jits,
    or a recompile of a key already dispatched)."""
    def cost(c):
        return c.trace_s + c.lower_s + c.backend_s

    def entry(cs, **kw):
        top = max(cs, key=cost) if cs else None
        return dict(
            kw, fun_name=top and top.fun_name, compiles=len(cs),
            trace_s=sum(c.trace_s for c in cs),
            lower_s=sum(c.lower_s for c in cs),
            backend_s=sum(c.backend_s for c in cs),
            retrieval_s=sum(c.retrieval_s for c in cs),
            cache=top and top.cache)

    spans = lifecycle_rows(lo, hi)
    log = compiles(lo, hi)
    calls = [r for r in spans if r.name == "program.first_call"]
    builds = [r for r in spans if r.name != "program.first_call"]
    table, claimed = [], set()
    for r in calls:
        mine = [c for c in log if r.t0 <= c.t_end <= r.t1]
        claimed.update(map(id, mine))
        span_s = r.t1 - r.t0
        table.append(entry(
            mine, program=r.attrs.get("program"), key=r.attrs.get("key"),
            span=r.name, t_end=r.t1, span_s=span_s,
            first_run_s=span_s - sum(map(cost, mine))))
    for c in log:
        if id(c) not in claimed:
            inside = [r.name for r in builds if r.t0 <= c.t_end <= r.t1]
            table.append(entry(
                [c], program=None, key=None,
                span=inside[-1] if inside else None, t_end=c.t_end,
                span_s=None, first_run_s=None))
    return table
