"""Reduction of a JAX profiler trace to the numbers the per-layer metrics
read.

The profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it with nothing but JAX.  What is taken from it:

* device planes (``/device:TPU:<n>``): every event on their ``XLA Ops``
  line is an operation running on that chip.  Busy time is the union of
  those intervals inside the window, averaged over the chips; the traversal
  kernel's time is the sum of the durations of its events.  On the chip
  an op event is named by its HLO instruction text; the op's name is the
  part before `` = `` and the kernel is the one custom call of the path,
  whose text names ``custom_call_target="tpu_custom_call"``
  (``KERNEL_MARKS``);
* host planes: the benchmark's own ``TraceAnnotation`` spans, all named
  ``bench.*`` — ``bench.window`` around the measured window, ``bench.call``
  around each call into the server.  They share the device's clock in the trace, so
  an idle gap of the device is put down to the innermost host span that
  covers its middle.

``summarize`` is pure: it takes plain ``(name, start_ns, end_ns)`` events,
so the tests drive it with a small recorded trace.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

#: substrings that mark the traversal kernel's device events
KERNEL_MARKS = ("tpu_custom_call",)
#: device planes of TensorCores (not their SparseCores or host offload)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."


@dataclass
class Event:
    name: str
    start_ns: float
    end_ns: float
    detail: str = ""  # HLO op name / long name, where the trace gives one

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclass
class Summary:
    """What the per-layer readers take from one traced window."""

    window_s: float
    busy_s: float  # union of device op intervals, averaged over chips
    n_devices: int
    kernel_s: float  # summed device durations of the kernel's events
    kernel_events: int
    device_ops: list = field(default_factory=list)  # [[name, s]] top 10
    idle_gaps: list = field(default_factory=list)  # [[host span, s]] top 10
    calls: list = field(default_factory=list)  # [(dur_s, busy_in_call_s)]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _overlap(intervals, lo: float, hi: float) -> float:
    return sum(e - s for s, e in _clip(intervals, lo, hi))


def is_kernel(ev: Event) -> bool:
    text = f"{ev.name} {ev.detail}"
    return any(mark in text for mark in KERNEL_MARKS)


def summarize(devices: dict[str, list[Event]], host: list[Event]) -> Summary | None:
    """Reduce device op events (per device plane) and the benchmark's host
    spans; ``None`` when the trace holds no ``bench.window`` span or no
    device operation inside it."""
    windows = [e for e in host if e.name == "bench.window"]
    if not windows or not devices:
        return None
    lo = min(e.start_ns for e in windows)
    hi = max(e.end_ns for e in windows)
    window_ns = hi - lo
    busy_per_dev = []
    merged: list[tuple[float, float]] = []
    op_time: dict[str, float] = defaultdict(float)
    kernel_ns, kernel_n = 0.0, 0
    for events in devices.values():
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
        u = _union(_clip([(e.start_ns, e.end_ns) for e in inside], lo, hi))
        busy_per_dev.append(sum(e - s for s, e in u))
        merged.extend(u)
        for e in inside:
            op_time[e.name] += e.dur_ns
            if is_kernel(e):
                kernel_ns += e.dur_ns
                kernel_n += 1
    busy_ns = sum(busy_per_dev) / len(busy_per_dev)
    if window_ns <= 0 or busy_ns <= 0:
        return None
    busy = _union(merged)
    # idle gaps of the device, each put down to the innermost host span
    # that covers its middle
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [e for e in host if e.name != "bench.window"]
    idle: dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [h for h in spans if h.start_ns <= mid < h.end_ns]
        label = (min(covering, key=lambda h: h.dur_ns).name
                 if covering else "bench.window")
        idle[label] += e - s
    calls = [
        (c.dur_ns / 1e9, _overlap(busy, c.start_ns, c.end_ns) / 1e9)
        for c in host
        if c.name == "bench.call" and c.start_ns >= lo and c.end_ns <= hi
    ]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gap_top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return Summary(
        window_s=window_ns / 1e9, busy_s=busy_ns / 1e9,
        n_devices=len(devices), kernel_s=kernel_ns / 1e9,
        kernel_events=kernel_n,
        device_ops=[[n, v / 1e9] for n, v in top],
        idle_gaps=[[n, v / 1e9] for n, v in gap_top],
        calls=calls,
    )


def _detail(ev) -> str:
    parts = []
    try:
        for key, value in ev.stats:
            if isinstance(value, str):
                parts.append(value)
    except (TypeError, ValueError):
        pass
    return " ".join(parts)


def read_xplane(path: Path) -> tuple[dict[str, list[Event]], list[Event]]:
    """Device op events per device plane, and the ``bench.*`` host spans,
    of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: dict[str, list[Event]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, _, rest = ev.name.partition(" = ")
                    evs.append(Event(name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     f"{rest} {_detail(ev)}"))
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append(Event(ev.name, ev.start_ns,
                                          ev.start_ns + ev.duration_ns))
    return devices, host


def find_xplane(log_dir: Path) -> Path | None:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    return found[-1] if found else None

