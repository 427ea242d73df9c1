"""Named host spans of the program, recorded only while a JAX profiler
session is active.

``span(name)`` marks one stage of the work (``sweep.pair``,
``davidson.read``, ``split``, ...).  With no profiler session it returns one
shared no-op context: no allocation, no clock read, no annotation.  Under a
session (``jax.profiler.trace``, ``jax.profiler.start_trace`` or an
in-memory ``ProfilerSession``) a span

- enters a ``jax.profiler.TraceAnnotation`` of its name, so the profile
  shows it on the same clock as the device's operations, and
- appends ``(name, parent, start_ns, end_ns)`` to an in-memory record, on
  ``time.perf_counter_ns``; ``parent`` is the index in the record of the
  enclosing span on the same thread, or ``None``.

The record is process-wide, like the profiler session that switches it on:
``records()`` reads it, ``reset()`` clears it, and ``per_root`` groups it by
a root span for the readers of per-stage host time.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from jax._src.lib import _profiler
from jax.profiler import TraceAnnotation

Record = Tuple[str, Optional[int], int, Optional[int]]

_live = _profiler.TraceMe.is_enabled
_records: List[list] = []
_lock = threading.Lock()
_open = threading.local()  # .stack: indices of this thread's open spans


class _Off:
    """The context ``span`` returns when no profiler session is active."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("_name", "_ann", "_rec", "_stack")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._ann = TraceAnnotation(self._name)
        self._ann.__enter__()
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self._stack = stack
        parent = stack[-1] if stack else None
        with _lock:
            index = len(_records)
            self._rec = [self._name, parent, time.perf_counter_ns(), None]
            _records.append(self._rec)
        stack.append(index)
        return self

    def __exit__(self, *exc):
        self._stack.pop()
        end = time.perf_counter_ns()
        self._ann.__exit__(None, None, None)
        self._rec[3] = end
        return False


def span(name: str):
    """Context manager for one named stage; recorded only while a JAX
    profiler session is active."""
    return _Span(name) if _live() else _OFF


def records() -> List[Record]:
    """Every span recorded since the last ``reset``, in the order they
    opened; a span still open has ``end_ns`` ``None``."""
    with _lock:
        return [tuple(r) for r in _records]


def reset() -> None:
    """Clear the record.  Call it with no span open: the parent index of a
    span opened before it would point into the cleared record."""
    with _lock:
        _records.clear()


def per_root(root: str, recs: Optional[Sequence[Record]] = None) -> List[Dict]:
    """For each finished span named ``root``, in order: ``{"ns": its
    duration, "spans": {name: [total_ns, count]}}`` over the finished spans
    nested under it at any depth.  A span belongs to its innermost enclosing
    ``root``.  ``recs`` defaults to ``records()``."""
    recs = records() if recs is None else recs
    owner: List[Optional[int]] = [None] * len(recs)
    out: Dict[int, Dict] = {}
    for i, (name, parent, start, end) in enumerate(recs):
        if name == root:
            owner[i] = i
            if end is not None:
                out[i] = {"ns": end - start, "spans": {}}
            continue
        top = owner[i] = owner[parent] if parent is not None else None
        if top in out and end is not None:
            total = out[top]["spans"].setdefault(name, [0, 0])
            total[0] += end - start
            total[1] += 1
    return [out[i] for i in sorted(out)]
