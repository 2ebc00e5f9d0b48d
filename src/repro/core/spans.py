"""Named host spans at the runtime's layer boundaries.

``with span("acs.plan"):`` does two things:

* while a profiler is recording, it enters a
  ``jax.profiler.TraceAnnotation`` of the same name, so the span lands on
  the trace's host plane on the same clock as the device's ops (keyword
  arguments become the annotation's metadata, e.g. a request id);
* always, it adds to a process-wide table, per name: how many spans
  closed, their total seconds, and their self seconds (total less the
  time covered by spans opened inside them on the same thread).

The table is always on: an operator polls it through
``DeviceSession.session_stats()["spans"]`` on a live server, and a
benchmark reads the difference of two snapshots. A span costs a clock
pair and a few list and dict operations on its thread's own stack and
table (no lock), so spans go around phases (an epoch's planning,
lowering, launch, sync), never around single small tasks, except where
one task is the phase (a served token's host-path decode).

Each thread keeps its own stack of open spans, so threaded sessions
nest correctly, and its own rows, which ``snapshot`` sums. A span whose
body raises is recorded all the same.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List

import jax

__all__ = ["span", "snapshot"]

#: The clock spans read (module-level so tests can substitute one).
_clock = time.perf_counter

_annotation = jax.profiler.TraceAnnotation
_profiling = _annotation.is_enabled

# Every thread's table that has recorded a span: name -> [count, total
# seconds, self seconds]. Appended under the lock, once per thread.
_tables: List[Dict[str, List[float]]] = []
_tables_lock = threading.Lock()


class _ThreadState(threading.local):
    def __init__(self) -> None:
        self.stack: List["span"] = []
        self.table: Dict[str, List[float]] = {}
        with _tables_lock:
            _tables.append(self.table)


_state = _ThreadState()


class span:
    """Context manager: one named, self-timed span (see the module doc)."""

    __slots__ = ("name", "meta", "_t0", "_child", "_ann")

    def __init__(self, name: str, **meta: Any) -> None:
        self.name = name
        self.meta = meta

    def __enter__(self) -> "span":
        _state.stack.append(self)
        self._child = 0.0
        if _profiling():
            self._ann = _annotation(self.name, **self.meta)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = _clock()
        return self

    def __exit__(self, exc_type: Any, exc: Any, tb: Any) -> None:
        total = _clock() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        state = _state
        stack = state.stack
        stack.pop()
        if stack:
            stack[-1]._child += total
        try:
            row = state.table[self.name]
        except KeyError:
            row = state.table[self.name] = [0, 0.0, 0.0]
        row[0] += 1
        row[1] += total
        row[2] += total - self._child


def snapshot() -> Dict[str, Dict[str, float]]:
    """Every span name recorded so far in this process, summed over its
    threads: ``{name: {"n", "total_s", "self_s"}}`` (cumulative; callers
    difference two snapshots for an interval)."""
    out: Dict[str, Dict[str, float]] = {}
    with _tables_lock:
        tables = list(_tables)
    for table in tables:
        for name, (n, total, own) in list(table.items()):
            acc = out.setdefault(name, {"n": 0, "total_s": 0.0, "self_s": 0.0})
            acc["n"] += int(n)
            acc["total_s"] += total
            acc["self_s"] += own
    return out
