"""Host spans of the serving loop, always on.

``with rec.span(name, **args):`` enters a
``jax.profiler.TraceAnnotation``, so under the profiler the span lands
on the host plane of the trace, on the clock of the device's events;
its ``args`` are formatted only while the profiler records.  It also
adds its host time to ``rec.totals[name] = [calls, seconds]``.

The recorder keeps the host gap too: the host time from the return of
a device sync (a ``serve.sync`` span: the host blocked until the device
had drained) to the next jitted call (:meth:`Recorder.dispatch`, called
right before it), less the time spent in ``serve.wait`` (nothing to
run).  And, inside :meth:`Recorder.listening`, it lists the programs
XLA compiled or loaded from the persistent cache.  DESIGN.md §7 lists
the span names.
"""
from __future__ import annotations

import contextlib
import time

import jax
from jax.profiler import TraceAnnotation

__all__ = ["Recorder"]

SYNC = "serve.sync"
WAIT = "serve.wait"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Recorder:
    """Per-span totals, the host gap and the compiled programs of one
    ``ServeLoop.run``.  ``clock`` is injectable for tests."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.totals: dict[str, list] = {}
        self.gap_s = 0.0
        self.compiled: list[str] = []
        # the first sync return since the last dispatch (None: no gap
        # open), and the part of the open gap that lay before a wait
        self._drained: float | None = None
        self._held = 0.0

    def span(self, name: str, **args) -> _Span:
        return _Span(self, name, args)

    def dispatch(self) -> None:
        """Mark a jitted call about to be entered: it closes the gap."""
        if self._drained is not None:
            self.gap_s += self._held + self.clock() - self._drained
            self._drained = None
            self._held = 0.0

    def _close(self, name: str, t0: float, t1: float) -> None:
        tot = self.totals.get(name)
        if tot is None:
            tot = self.totals[name] = [0, 0.0]
        tot[0] += 1
        tot[1] += t1 - t0
        if name == SYNC:
            if self._drained is None:
                self._drained = t1
        elif name == WAIT and self._drained is not None:
            self._held += t0 - self._drained
            self._drained = t1

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == COMPILE_EVENT:
            self.compiled.append(str(kw.get("fun_name", "")))

    @contextlib.contextmanager
    def listening(self):
        """List every program compiled while the block runs."""
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(
                self._on_duration)


class _Span:
    """One host span; ``set_metadata`` adds arguments known only inside
    it."""

    __slots__ = ("rec", "name", "args", "_ann", "_t0")

    def __init__(self, rec: Recorder, name: str, args: dict):
        self.rec = rec
        self.name = name
        self.args = args

    def __enter__(self):
        on = TraceAnnotation.is_enabled()
        self._ann = TraceAnnotation(self.name, **(self.args if on else {}))
        self._ann.__enter__()
        self._t0 = self.rec.clock()
        return self

    def set_metadata(self, **args) -> None:
        if TraceAnnotation.is_enabled():
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        self.rec._close(self.name, self._t0, self.rec.clock())
        self._ann.__exit__(*exc)
        return False
