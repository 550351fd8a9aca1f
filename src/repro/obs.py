"""In-process telemetry: spans, counters, the fleet engine's call log and
JAX's compile events, all kept in bounded memory in this process.

* ``span(name)`` times a block on ``time.perf_counter``.  It also opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so a profiler capture
  shows the span on its host plane, on the device planes' clock.
* ``count(name, n)`` adds to a named counter; ``counter(name)`` reads one.
* ``record_call(record)`` appends one ``run_fleet`` call's record (its span
  durations and work counts, see ``fl.simulator``) to the call log;
  ``calls()`` reads the log back.
* A listener on ``jax.monitoring`` keeps the interval of every tracing,
  lowering and backend compile (a load from the persistent cache is timed
  as a backend compile); ``compile_seconds(until)`` is the wall time the
  intervals ending by ``until`` cover.

Names start with ``repro.``.  Recording is always on: a span costs two
clock reads and one annotation, a counter one locked add.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time

import jax

CALL_LOG = 256
COMPILE_LOG = 65536

# jax.monitoring's duration events of the compile pipeline.
COMPILE_EVENTS = frozenset({
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
})


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float | None = None      # set when the block exits

    @property
    def seconds(self) -> float:
        return self.end - self.start


_lock = threading.Lock()
_calls: collections.deque = collections.deque(maxlen=CALL_LOG)
# (start, end) on time.perf_counter of each compile event.
_compiles: collections.deque = collections.deque(maxlen=COMPILE_LOG)
_counters: collections.Counter = collections.Counter()


@contextlib.contextmanager
def span(name: str):
    """Time the block as span ``name``; yields the ``Span``, whose ``end``
    is set when the block exits."""
    if not name.startswith("repro."):
        raise ValueError(f"span names start with 'repro.', got {name!r}")
    s = Span(name, time.perf_counter())
    try:
        with jax.profiler.TraceAnnotation(name):
            yield s
    finally:
        s.end = time.perf_counter()


def count(name: str, n: int | float = 1) -> None:
    with _lock:
        _counters[name] += n


def counter(name: str) -> int | float:
    return _counters[name]


def record_call(record: dict) -> None:
    _calls.append(record)


def calls() -> list[dict]:
    """The call log, oldest first (at most ``CALL_LOG`` records)."""
    return list(_calls)


def compile_seconds(until: float | None = None) -> float:
    """Seconds covered by the tracing, lowering and backend-compile events
    that ended by ``until`` (all of them without it).  A function traced
    while another is traced nests inside it, so the events' union is
    taken, not their sum."""
    total, lo, hi = 0.0, None, None
    for s, t in sorted(e for e in list(_compiles)
                       if until is None or e[1] <= until):
        if hi is not None and s <= hi:
            hi = max(hi, t)
            continue
        if hi is not None:
            total += hi - lo
        lo, hi = s, t
    return total if hi is None else total + hi - lo


def reset(*names: str) -> None:
    """Clear the named counters."""
    with _lock:
        for name in names:
            _counters.pop(name, None)


def _on_duration(event: str, duration: float, **_) -> None:
    if event in COMPILE_EVENTS:
        end = time.perf_counter()
        _compiles.append((end - duration, end))


jax.monitoring.register_event_duration_secs_listener(_on_duration)
