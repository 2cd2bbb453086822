"""Named spans of the serving loop, on the profiler's clock.

A :class:`LoopSpan` opens a ``jax.profiler.TraceAnnotation`` under its name,
so a profiler trace shows it beside the device programs it dispatched,
and reads ``time.monotonic()`` at both ends — the clock the timeline and
the request tracer use. Its child phases (``span.phase("dispatch")``)
are annotations named ``<span>.<phase>`` whose ``[t0, t1]`` the parent
keeps in ``phases``, so a wave or step can write its own boundaries and
its phases' into the scheduler timeline.

Nothing here waits for the device: every end time is a point the host
reaches anyway. :data:`NO_SPAN` is the off path — one shared object whose
``phase`` returns itself, so a disabled bundle reads no clock, allocates
nothing and opens no annotation.
"""
from __future__ import annotations

import time


class _NoSpan:
    """The shared no-op span and phase."""

    __slots__ = ()
    live = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def phase(self, name: str) -> "_NoSpan":
        return self

    def note(self, **fields) -> None:
        pass


NO_SPAN = _NoSpan()


class LoopSpan:
    """One live span: a profiler annotation with monotonic ends, the
    boundaries of its child phases, and the fields the caller noted."""

    __slots__ = ("name", "t0", "t1", "phases", "fields", "_ann")
    live = True

    def __init__(self, name: str, fields: dict):
        self.name = name
        self.t0 = self.t1 = 0.0
        self.phases: dict[str, list[float]] = {}
        self.fields = fields

    def __enter__(self) -> "LoopSpan":
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        self._ann.__exit__(*exc)
        return False

    def phase(self, name: str) -> "_Phase":
        """A child span ``<name>.<phase>``; its ends land in ``phases``."""
        return _Phase(self, name)

    def note(self, **fields) -> None:
        """Add fields to the span's timeline entry."""
        self.fields.update(fields)

    def record(self) -> dict:
        """The span as a timeline field: ends, phases and noted fields."""
        return {"t0": self.t0, "t1": self.t1, "phases": self.phases,
                **self.fields}


class _Phase:
    __slots__ = ("parent", "name", "t0", "_ann")

    def __init__(self, parent: LoopSpan, name: str):
        self.parent, self.name = parent, name

    def __enter__(self) -> "_Phase":
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(f"{self.parent.name}.{self.name}")
        self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        t1 = time.monotonic()
        self._ann.__exit__(*exc)
        self.parent.phases[self.name] = [self.t0, t1]
        return False
