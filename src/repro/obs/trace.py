"""Nesting span tracer with Chrome trace-event JSON export.

The tracer is the timing half of :mod:`repro.obs`: ``with
tracer.span("pack"): ...`` records one *complete* event per exit on a
single ``perf_counter`` timebase, and :meth:`Tracer.chrome_trace`
serializes the session as Chrome trace-event JSON — the format Perfetto
(https://ui.perfetto.dev) and ``chrome://tracing`` open directly.

One span primitive
------------------
:class:`Span` is the only timing code path. ``Tracer.span``, the
engine recorders' stage spans (which also credit their seconds to a
stage) and :func:`stopwatch` (which times even when telemetry is off)
all build one. While it is recorded, a span also opens a
``jax.profiler.TraceAnnotation`` named ``repro.<span name>``
(:data:`PROFILER_PREFIX`), so under ``jax.profiler`` it lands in the
host plane on the same clock as the device operations. Each recorded
event's args carry ``call`` (the index in ``Telemetry.match_calls`` of
the engine call the span belongs to: the one open or last opened) and
``parent`` (the name of the enclosing span on the same thread, from the
tracer's per-thread stack; None at top level).

Zero-overhead-when-disabled contract
------------------------------------
The disabled path never touches this module's classes: ``NULL_SPAN`` is
one shared, reentrant no-op context manager and the disabled telemetry
facade returns it by identity from every ``span()`` call — no event
list, no timestamping, no per-call object, no profiler annotation and
no ``jax`` import. Hot loops may call ``telemetry.span(...)``
unconditionally.
"""
from __future__ import annotations

import json
import threading
import time

#: Prefix of the profiler annotation each recorded span opens.
PROFILER_PREFIX = "repro."

def _annotation(name: str):
    """The profiler annotation of a recorded span (``jax.profiler`` is
    imported here, on the enabled path only)."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(PROFILER_PREFIX + name)


class _NullSpan:
    """Shared no-op context manager — the entire disabled span path.

    A single module-level instance (:data:`NULL_SPAN`) is returned for
    every disabled ``span()`` call; it is stateless, reentrant, and
    allocation-free on entry/exit.
    """

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One timed block (context manager) — the one timing path.

    ``seconds`` holds the block's ``perf_counter`` duration after exit.
    With a ``tracer`` the span is recorded: it opens the profiler
    annotation ``repro.<name>`` around the block, sits on the tracer's
    per-thread stack while open, and appends one complete event at
    exit. Without one (:func:`stopwatch` with telemetry off) it only
    times. ``credit`` is an optional ``(dict, key)`` the seconds are
    added to at exit — how an engine recorder's stage spans fill
    ``stage_seconds``.
    """

    __slots__ = ("_tracer", "name", "args", "_credit", "_annotation", "t0", "seconds")

    def __init__(self, tracer: "Tracer | None", name: str, args: dict | None = None,
                 credit: tuple | None = None):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._credit = credit
        self._annotation = None
        self.t0 = 0.0
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        if self._tracer is not None:
            self._tracer._stack().append(self.name)
            self._annotation = _annotation(self.name)
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self.seconds = t1 - self.t0
        tracer = self._tracer
        if tracer is not None:
            self._annotation.__exit__(None, None, None)
            stack = tracer._stack()
            stack.pop()
            args = dict(self.args or ())
            args["call"] = tracer.call
            args["parent"] = stack[-1] if stack else None
            tracer.complete(self.name, self.t0, t1, args)
        if self._credit is not None:
            totals, key = self._credit
            totals[key] += self.seconds
        return False


class Tracer:
    """Collects spans + instants and exports Chrome trace-event JSON.

    All timestamps are ``perf_counter`` seconds relative to the
    tracer's construction (``epoch``), exported as microseconds — the
    trace-event ``ts`` unit. One tracer = one trace file. ``call`` is
    the index of the engine call the next spans belong to (set by the
    engine recorder, :func:`repro.obs.recorder`; None before the first).
    """

    def __init__(self):
        self.epoch = time.perf_counter()
        self.events: list[dict] = []
        self.call: int | None = None
        self._tids: dict[int, int] = {}
        self._local = threading.local()

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def _stack(self) -> list:
        """This thread's stack of open span names."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def span(self, name: str, **args) -> Span:
        """``with tracer.span("pack"): ...`` — records one complete event."""
        return Span(self, name, args or None)

    def complete(self, name: str, t0: float, t1: float, args: dict | None = None):
        """Record a measured span as a complete (``ph: "X"``) event."""
        ev = {
            "name": name,
            "cat": "obs",
            "ph": "X",
            "ts": (t0 - self.epoch) * 1e6,
            "dur": max(t1 - t0, 0.0) * 1e6,
            "pid": 0,
            "tid": self._tid(),
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, **args):
        """Record a zero-duration (instant) event — structured telemetry."""
        ev = {
            "name": name,
            "cat": "obs",
            "ph": "i",
            "s": "t",
            "ts": (time.perf_counter() - self.epoch) * 1e6,
            "pid": 0,
            "tid": self._tid(),
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    def chrome_trace(self, metadata: dict | None = None) -> dict:
        """The session as a Chrome trace-event JSON object (dict)."""
        trace = {
            "traceEvents": list(self.events),
            "displayTimeUnit": "ms",
        }
        if metadata:
            trace["otherData"] = dict(metadata)
        return trace

    def write_chrome_trace(self, path, metadata: dict | None = None) -> None:
        """Write the trace to ``path`` — open it at https://ui.perfetto.dev."""
        with open(path, "w") as f:
            json.dump(self.chrome_trace(metadata), f)
            f.write("\n")


def stopwatch(telemetry, name: str, **args) -> Span:
    """A :class:`Span` that times the block even when telemetry is off.

    For durations that must exist either way (the
    ``WaveSchedule.schedule_seconds`` / ``pack_seconds`` compatibility
    fields): ``seconds`` is always set, and the span is recorded (event
    and profiler annotation) only when ``telemetry`` is enabled — one
    measurement, never two timing code paths.
    """
    enabled = telemetry is not None and telemetry.enabled
    return Span(telemetry.tracer if enabled else None, name, args or None)
