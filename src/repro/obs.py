"""Spans: named, timed phases of the engine, recorded in memory.

``span(name, **attrs)`` marks one phase (``session.run``,
``engine.dispatch``, ``bridge.lower``, ...).  While recording, each span
leaves one :class:`Span` record in a process-wide list that :func:`spans`
returns and :func:`reset` clears; nothing is written to a file.

Recording is on inside ``with recording():`` and while a JAX profiler
trace is being captured (the flag JAX's own ``TraceAnnotation`` checks).
While the profiler runs, each span also enters a ``TraceAnnotation``
named ``repro:<name>``, so the profiler's trace holds the program's
phases beside the device's operations.  Spans are stamped with
``time.time_ns()``, the clock the profiler stamps its host events with:
an event's offset in a ``.xplane.pb`` plus the trace's
``profile_start_time`` gives the same nanosecond.

Off, a span is one flag check and a shared no-op context manager: no
clock read and no record.  Spans go at layer boundaries, never inside
per-instruction or per-row loops.  Records are kept for one thread at a
time (the engine is single-threaded).
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time

__all__ = ["Span", "span", "recording", "spans", "reset"]

PREFIX = "repro:"


@dataclasses.dataclass
class Span:
    """One recorded span.  ``parent`` is the ``id`` of the span open
    around it, or None; ``attrs`` holds small ints, strs and bools."""

    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int | None
    attrs: dict

    @property
    def wall_ns(self) -> int:
        return self.end_ns - self.start_ns


_records: list[Span] = []
_ids = itertools.count()
_open: list[int] = []            # ids of the spans open, innermost last
_recording = 0                   # depth of nested recording() blocks


def _profiler_flag():
    """JAX's 'a trace is being captured' check, or None when this JAX
    has none (then only recording() turns spans on)."""
    try:
        from jax._src.lib import _profiler
        return _profiler.TraceMe.is_enabled
    except (ImportError, AttributeError):
        return None


_profiling = _profiler_flag() or (lambda: False)


class _Noop:
    """The span handed out while nothing records.  It is falsy, so a
    caller can skip working out attributes: ``if sp: sp.set(...)``."""

    __slots__ = ()

    def __bool__(self) -> bool:
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _Noop()


class _Live:
    __slots__ = ("rec", "annotation")

    def __init__(self, name: str, attrs: dict):
        self.rec = Span(name, 0, 0, next(_ids), None, attrs)
        self.annotation = None

    def __enter__(self):
        if _profiling():
            import jax
            self.annotation = jax.profiler.TraceAnnotation(
                PREFIX + self.rec.name)
            self.annotation.__enter__()
        self.rec.parent = _open[-1] if _open else None
        _records.append(self.rec)
        _open.append(self.rec.id)
        self.rec.start_ns = time.time_ns()
        return self

    def __exit__(self, *exc):
        self.rec.end_ns = time.time_ns()
        _open.pop()
        if self.annotation is not None:
            self.annotation.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        """Attributes known only once the span's work has run."""
        self.rec.attrs.update(attrs)


def span(name: str, **attrs):
    """Context manager timing one phase; ``as sp`` gives ``sp.set(...)``."""
    if not (_recording or _profiling()):
        return _NOOP
    return _Live(name, attrs)


@contextlib.contextmanager
def recording():
    """Record spans inside this block (as well as under the profiler)."""
    global _recording
    _recording += 1
    try:
        yield
    finally:
        _recording -= 1


def spans() -> list[Span]:
    """Every span recorded since the last :func:`reset`, in start order."""
    return list(_records)


def reset() -> None:
    _records.clear()
