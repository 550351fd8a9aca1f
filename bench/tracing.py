"""Host spans and the profiler trace of a ``--trace 1`` run, and the
reduction from that trace to busy time, idle time, kernel time and the
breakdown.

The benchmark's files open every span themselves (``Tracer.span``): a host
timer plus a ``jax.profiler.TraceAnnotation`` of the same name, so the
trace can say what the host was doing in each idle gap of the device.  The
reduction reads the ``.xplane.pb`` file with ``jax.profiler.ProfileData``:

* device planes are ``/device:TPU:<id>``; their ``XLA Ops`` line holds one
  event per operation run, their ``XLA Modules`` line one per program run;
* the traced window is the host event ``bench.window``;
* busy time is the union of the operation intervals inside the window, per
  device (of the program runs, where a scanned sweep holds more operations
  than ``MAX_OP_EVENTS``); the idle share is 1 - busy / window;
* a device plane in which the profiler marks dropped buffers fails the
  run: its busy time would count the gap as idle.

The profiler's buffers on a TPU v5e hold about 6.3 million operation
events, whatever libtpu's ``tpu_trace_mode``, and it takes some 26 us an
event to stop a capture: a driver keeps its traced work under that (the
sweep profiles one chunk of episodes a chip), and a capture that still
overflows fails.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import re
import shutil
import time
from pathlib import Path

from bench.harness import BenchError

WINDOW = "bench.window"
# The profiler's own marker where it dropped events to keep its output
# under the 2 GB proto limit: a trace that holds one is not complete.
DROPPED = "Trace Buffers Dropped"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# Operation events read per device.  A scanned sweep runs millions of loop
# trips; past this many the operations are a sample for the breakdown, and
# busy time comes from the program events instead.
MAX_OP_EVENTS = 400_000
# Operations that only hold others (loop bodies run inside them): busy time
# counts them, the breakdown of operations does not.
_CONTAINERS = ("%while", "%conditional", "%call")


class NullTracer:
    """The ``--trace 0`` tracer: spans cost nothing and record nothing."""

    enabled = False

    @contextlib.contextmanager
    def span(self, name: str):
        yield

    def start(self) -> None:
        pass

    def stop(self) -> None:
        pass

    def reduce(self):
        return None


class Tracer:
    """Host spans (seconds, by name) and one profiler capture."""

    enabled = True

    def __init__(self, root: Path, workload: str, seed: int, devices):
        self.dir = root / ".bench_traces" / f"{workload}-{seed}"
        self.device_ids = [d.id for d in devices]
        self.spans: dict[str, list[float]] = {}
        self._window = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t0)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0    # the spans are TraceAnnotations
        jax.profiler.start_trace(str(self.dir), profiler_options=options)
        self._window = self.span(WINDOW)
        self._window.__enter__()

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        print(f"[trace] stop_trace {time.perf_counter() - t0:.1f} s",
              flush=True)

    def reduce(self) -> "TraceSummary | None":
        files = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        if not files:
            return None
        t0 = time.perf_counter()
        try:
            return summarize(files[-1], self.device_ids)
        finally:
            print(f"[trace] {files[-1].stat().st_size} bytes read and "
                  f"reduced in {time.perf_counter() - t0:.1f} s", flush=True)
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Event:
    name: str
    start: float    # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class TraceSummary:
    window: tuple[float, float]
    ops: dict[int, list[Event]]        # device id -> operation events
    modules: dict[int, list[Event]]    # device id -> program events
    host: list[Event]                  # the benchmark's own spans
    ops_complete: dict[int, bool] = dataclasses.field(default_factory=dict)

    def busy_events(self, dev: int) -> list[Event]:
        """What busy time is read from: every operation where all were
        read, else the program runs."""
        if self.ops_complete.get(dev, True):
            return self.ops.get(dev, [])
        return self.modules.get(dev, [])

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Busy seconds inside the window, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(busy_seconds(self.busy_events(d), self.window)
                   for d in self.ops) / len(self.ops)

    def idle_share(self) -> float | None:
        if not self.ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s / self.window_s

    def op_events(self, pattern: str) -> list[Event]:
        """Operation events, on every device, whose name matches."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [e for evs in self.ops.values() for e in evs
                if rx.search(e.name) and e.start >= lo and e.end <= hi]

    def module_events(self, pattern: str) -> list[Event]:
        rx = re.compile(pattern)
        lo, hi = self.window
        return [e for evs in self.modules.values() for e in evs
                if rx.search(e.name) and e.start >= lo and e.end <= hi]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (seconds, averaged
        over devices) and the device's idle time by what the host was
        doing meanwhile (the innermost benchmark span around the gap)."""
        n_dev = max(len(self.ops), 1)
        by_op: dict[str, float] = {}
        for evs in self.ops.values():
            for e in clip(evs, self.window):
                if not e.name.startswith(_CONTAINERS):
                    by_op[e.name] = by_op.get(e.name, 0.0) + (e.end - e.start)
        ops = sorted(((k, v / n_dev) for k, v in by_op.items()),
                     key=lambda kv: -kv[1])[:top]
        by_host: dict[str, float] = {}
        for d in self.ops:
            for lo, hi in idle_gaps(self.busy_events(d), self.window):
                name = host_activity(self.host, 0.5 * (lo + hi))
                by_host[name] = by_host.get(name, 0.0) + (hi - lo)
        gaps = sorted(((k, v / n_dev) for k, v in by_host.items()),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def clip(events: list[Event], window: tuple[float, float]) -> list[Event]:
    lo, hi = window
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t))
    return out


def merged(events: list[Event], window: tuple[float, float]):
    """Union of the event intervals inside the window, sorted."""
    spans = sorted((e.start, e.end) for e in clip(events, window))
    out: list[list[float]] = []
    for s, t in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_seconds(events: list[Event], window: tuple[float, float]) -> float:
    return sum(t - s for s, t in merged(events, window))


def idle_gaps(events: list[Event], window: tuple[float, float]):
    """The intervals of the window in which no operation ran."""
    lo, hi = window
    gaps, cursor = [], lo
    for s, t in merged(events, window):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, t)
    if hi > cursor:
        gaps.append((cursor, hi))
    return gaps


def host_activity(host: "HostSpans", at: float) -> str:
    """Name of the innermost benchmark span that holds the instant.

    The spans nest, so the innermost one holding the instant is the
    latest-starting one that has not ended.  The look goes back at most 64
    spans: the benchmark's spans of one step are never more."""
    last = bisect.bisect_right(host.starts, at) - 1
    for i in range(last, max(last - 64, -1), -1):
        e = host[i]
        if e.name != WINDOW and e.end >= at:
            return e.name
    return "outside any bench span"


class HostSpans(list):
    """Host spans sorted by start, with the starts kept for bisection."""

    def __init__(self, events):
        super().__init__(sorted(events, key=lambda e: e.start))
        self.starts = [e.start for e in self]


def summarize(path: Path, device_ids: list[int]) -> TraceSummary | None:
    """Read one ``.xplane.pb`` into the events the reduction needs."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: dict[int, list[Event]] = {}
    complete: dict[int, bool] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) in device_ids:
            dev = int(m.group(1))
            if dropped(plane):
                raise BenchError(f"the profiler dropped trace buffers on "
                                 f"{plane.name}: the trace is incomplete")
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = _events(line, MAX_OP_EVENTS)
                    complete[dev] = len(ops[dev]) < MAX_OP_EVENTS
                elif line.name == MODULES_LINE:
                    modules[dev] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(e for e in _events(line)
                            if e.name.startswith("bench."))
    windows = [e for e in host if e.name == WINDOW]
    if not windows:
        return None
    w = max(windows, key=lambda e: e.end - e.start)
    return TraceSummary(window=(w.start, w.end), ops=ops, modules=modules,
                        host=HostSpans(host), ops_complete=complete)


def dropped(plane) -> bool:
    """Whether the profiler marked a gap in a device plane.  The marker
    sits on a line of its own, never among the operations."""
    return any(ev.name == DROPPED for line in plane.lines
               if line.name != OPS_LINE for ev in line.events)


def _events(line, cap: int | None = None) -> list[Event]:
    """A line's events; an operation is named by its HLO instruction, the
    text before `` = ``."""
    out = []
    for ev in line.events:
        if cap is not None and len(out) >= cap:
            break
        start = ev.start_ns * 1e-9
        out.append(Event(ev.name.split(" = ", 1)[0], start,
                         start + ev.duration_ns * 1e-9))
    return out
