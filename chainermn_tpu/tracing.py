"""Spans inside the program, on the profiler's clock.

Tracing is on exactly while a ``jax.profiler`` session is active in the
process (``jax.profiler.start_trace``, the ``Profile`` trainer extension,
the benchmark's ``--trace 1``) and there is no other switch. On, a span is a
``jax.profiler.TraceAnnotation`` (an event on ``/host:CPU`` of the same
xplane as the device lines) AND a row ``(id, parent_id, name, t0, t1,
attrs)`` on ``time.perf_counter`` in a bounded in-memory deque. Off,
:func:`span` returns one shared no-op object: no clock read, no allocation.

Nothing is exported, written or aggregated here: self time, medians and
ratios are the readers' work (``benchmark/metrics/``, docs/serving.md).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["MAX_ROWS", "OFF", "Row", "clear", "rows", "span"]

#: rows kept; the oldest fall off (about ten rows a scheduler iteration)
MAX_ROWS = 1 << 16


class Row(NamedTuple):
    id: int
    parent_id: Optional[int]     # innermost span open on the thread
    name: str
    t0: float                    # time.perf_counter seconds
    t1: float
    attrs: dict


_rows: collections.deque = collections.deque(maxlen=MAX_ROWS)
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
    __slots__ = ("id", "parent_id", "name", "attrs", "t0", "_ann")

    def __init__(self, name, attrs):
        self.name, self.attrs = name, attrs

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
        _rows.append(Row(self.id, self.parent_id, self.name, self.t0, t1,
                         self.attrs))
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
    return _Span(name, attrs)


def rows(lo: Optional[float] = None, hi: Optional[float] = None):
    """The recorded rows that lie whole inside ``[lo, hi]``
    (``time.perf_counter`` seconds), oldest first."""
    return [r for r in _rows if (lo is None or r.t0 >= lo)
            and (hi is None or r.t1 <= hi)]


def clear() -> None:
    _rows.clear()
