"""The reduction from a profiler trace to the per-layer numbers."""
import math
import random
import time
from collections import defaultdict
from dataclasses import fields

import numpy as np
import pytest

from bench import devtrace
from bench.devtrace import Event


def _ev(name, s, e, detail=""):
    return Event(name, float(s), float(e), detail)


def test_summarize_a_small_recorded_trace():
    devices = {"/device:TPU:0": [
        _ev("fusion.1", 10, 30),
        _ev("%kernel.2", 20, 40, 'custom-call(), custom_call_target="tpu_custom_call"'),
        _ev("%kernel.2", 60, 70, 'custom_call_target="tpu_custom_call"'),
        _ev("fusion.1", 120, 130),  # after the window: not counted
    ]}
    host = [
        _ev("bench.window", 0, 100),
        _ev("bench.call", 5, 45),
        _ev("bench.wait", 45, 55),
        _ev("bench.call", 55, 75),
    ]
    s = devtrace.summarize(devices, host)
    assert s.window_s == pytest.approx(100e-9)
    # busy: [10, 40] and [60, 70]
    assert s.busy_s == pytest.approx(40e-9)
    assert s.idle_share == pytest.approx(0.6)
    assert s.kernel_s == pytest.approx(30e-9)
    assert s.kernel_events == 2
    # calls: 40 ns with 30 busy, 20 ns with 10 busy
    assert s.calls == [pytest.approx((40e-9, 30e-9)),
                       pytest.approx((20e-9, 10e-9))]
    # gaps [0, 10] (mid 5, inside the first call), [40, 60] (mid 50, the
    # wait), [70, 100] (no span but the window)
    gaps = dict(s.idle_gaps)
    assert gaps["bench.call"] == pytest.approx(10e-9)
    assert gaps["bench.wait"] == pytest.approx(20e-9)
    assert gaps["bench.window"] == pytest.approx(30e-9)
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx(20e-9)


def test_busy_is_averaged_over_chips():
    devices = {
        "/device:TPU:0": [_ev("a", 0, 50)],
        "/device:TPU:1": [_ev("a", 0, 10)],
    }
    s = devtrace.summarize(devices, [_ev("bench.window", 0, 100)])
    assert s.busy_s == pytest.approx(30e-9)
    assert s.n_devices == 2


def test_nothing_to_read_gives_none():
    assert devtrace.summarize({}, [_ev("bench.window", 0, 10)]) is None
    assert devtrace.summarize({"/device:TPU:0": [_ev("a", 0, 5)]}, []) is None
    # no device operation inside the window
    assert devtrace.summarize({"/device:TPU:0": [_ev("a", 20, 30)]},
                              [_ev("bench.window", 0, 10)]) is None


def test_read_xplane_finds_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0)
    f(jnp.ones(8)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(jnp.ones(8)).block_until_ready()
    path = devtrace.find_xplane(tmp_path)
    assert path is not None
    devices, host = devtrace.read_xplane(path)
    names = [e.name for e in host]
    assert "bench.window" in names and "bench.call" in names
    window = next(e for e in host if e.name == "bench.window")
    call = next(e for e in host if e.name == "bench.call")
    assert window.start_ns <= call.start_ns <= call.end_ns <= window.end_ns
    # the CPU has no TPU device plane: nothing for the device metrics
    assert devices == {}
    assert devtrace.summarize(devices, host) is None


# --- the reduction as it was first written: every host span scanned for
# every gap, the busy union clipped again for every call.  Kept as the
# reference that the sweeps in ``devtrace`` must agree with.

def _naive_union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _naive_clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _naive_overlap(intervals, lo, hi):
    return sum(e - s for s, e in _naive_clip(intervals, lo, hi))


def _naive_summarize(devices, host):
    windows = [e for e in host if e.name == "bench.window"]
    if not windows or not devices:
        return None
    lo = min(e.start_ns for e in windows)
    hi = max(e.end_ns for e in windows)
    window_ns = hi - lo
    busy_per_dev, merged = [], []
    op_time = defaultdict(float)
    kernel_ns, kernel_n = 0.0, 0
    for events in devices.values():
        inside = [e for e in events if e.end_ns > lo and e.start_ns < hi]
        u = _naive_union(_naive_clip([(e.start_ns, e.end_ns) for e in inside],
                                     lo, hi))
        busy_per_dev.append(sum(e - s for s, e in u))
        merged.extend(u)
        for e in inside:
            op_time[e.name] += e.dur_ns
            if devtrace.is_kernel(e):
                kernel_ns += e.dur_ns
                kernel_n += 1
    busy_ns = sum(busy_per_dev) / len(busy_per_dev)
    if window_ns <= 0 or busy_ns <= 0:
        return None
    busy = _naive_union(merged)
    gaps, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [e for e in host if e.name != "bench.window"]
    idle = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) / 2
        covering = [h for h in spans if h.start_ns <= mid < h.end_ns]
        label = (min(covering, key=lambda h: h.dur_ns).name
                 if covering else "bench.window")
        idle[label] += e - s
    calls = [
        (c.dur_ns / 1e9, _naive_overlap(busy, c.start_ns, c.end_ns) / 1e9)
        for c in host
        if c.name == "bench.call" and c.start_ns >= lo and c.end_ns <= hi
    ]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:10]
    gap_top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return devtrace.Summary(
        window_s=window_ns / 1e9, busy_s=busy_ns / 1e9,
        n_devices=len(devices), kernel_s=kernel_ns / 1e9,
        kernel_events=kernel_n,
        device_ops=[[n, v / 1e9] for n, v in top],
        idle_gaps=[[n, v / 1e9] for n, v in gap_top],
        calls=calls,
    )


def _assert_same(ours, naive):
    """Every field equal: names and order exactly, numbers within 1e-9
    relative."""
    assert (ours is None) == (naive is None)
    if naive is None:
        return

    def same(a, b, where):
        if isinstance(b, (list, tuple)):
            assert isinstance(a, (list, tuple)) and len(a) == len(b), where
            for i, (x, y) in enumerate(zip(a, b)):
                same(x, y, f"{where}[{i}]")
        elif isinstance(b, str):
            assert a == b, where
        else:
            assert math.isclose(a, b, rel_tol=1e-9, abs_tol=0.0), (where, a, b)

    for f in fields(devtrace.Summary):
        same(getattr(ours, f.name), getattr(naive, f.name), f.name)


SPAN_NAMES = ("bench.wait", "serve.call", "serve.prep", "serve.wait",
              "tree_predict.launch")
OP_NAMES = ("fusion.1", "copy.4", "%kernel.2")
KERNEL = 'custom_call_target="tpu_custom_call"'


def _random_trace(seed):
    """A small trace on a grid of quarter nanoseconds (exact in floats),
    made to hit the edge cases: nested and partly overlapping spans, twins
    of equal duration, calls and device ops across the window's edges, one
    or two device planes, and now and then a window the device fills."""
    rng = random.Random(seed)

    def q(a, b):
        return rng.randint(int(4 * a), int(4 * b)) / 4

    lo = 100.0
    hi = lo + rng.choice((40, 120, 300))
    host = [_ev("bench.window", lo, hi)]
    if rng.random() < 0.15:  # a second window span widens the window
        host.append(_ev("bench.window", q(lo, hi), hi + q(1, 10)))
    t = lo - q(0, 25)
    while t < hi + 10:
        end = t + q(4, 40)
        host.append(_ev("bench.call", t, end))
        for _ in range(rng.randint(0, 4)):  # nested, or running past the call
            s = q(t, end)
            host.append(_ev(rng.choice(SPAN_NAMES), s,
                            min(s + q(0.25, 20), end + rng.choice((0, 0, 5)))))
        t = end + q(0, 8)
    for _ in range(rng.randint(0, 3)):  # anywhere, overlapping what is there
        s = q(lo - 20, hi + 10)
        host.append(_ev(rng.choice(SPAN_NAMES), s, s + q(1, 60)))
    for _ in range(rng.randint(0, 3)):  # twins of equal duration
        h = rng.choice(host[1:])
        shift = rng.choice((0.0, 0.0, 0.25, -0.5))
        twin = _ev(rng.choice(SPAN_NAMES), h.start_ns + shift,
                   h.end_ns + shift)
        host.insert(rng.randint(1, len(host)), twin)
    devices = {}
    for plane in range(rng.choice((1, 1, 2))):
        ops = []
        for _ in range(rng.randint(1, 40)):
            s = q(lo - 30, hi + 20)
            name = rng.choice(OP_NAMES)
            ops.append(_ev(name, s, s + q(0, 15),
                           KERNEL if name == "%kernel.2" else ""))
        if rng.random() < 0.3:  # ops across both edges of the window
            ops += [_ev("copy.4", lo - q(1, 5), lo + q(1, 5)),
                    _ev("fusion.1", hi - q(1, 5), hi + q(1, 5))]
        if rng.random() < 0.1:  # the device busy all through the window
            ops.append(_ev("fusion.1", lo - 1, hi + 20))
        rng.shuffle(ops)
        devices[f"/device:TPU:{plane}"] = ops
    if rng.random() < 0.3:  # the edges left idle on every plane
        for ops in devices.values():
            ops[:] = [e for e in ops
                      if not (e.start_ns <= lo < e.end_ns
                              or e.start_ns < hi <= e.end_ns)]
        if not any(devices.values()):
            devices["/device:TPU:0"].append(_ev("copy.4", lo + 5, lo + 6))
    return devices, host


@pytest.mark.parametrize("seed", range(50))
def test_sweeps_agree_with_the_naive_reduction(seed):
    devices, host = _random_trace(seed)
    _assert_same(devtrace.summarize(devices, host),
                 _naive_summarize(devices, host))


def test_the_random_traces_reach_every_case():
    """The traces above cover each case the sweeps could get wrong."""
    seen = defaultdict(int)
    for seed in range(50):
        devices, host = _random_trace(seed)
        lo = min(e.start_ns for e in host if e.name == "bench.window")
        hi = max(e.end_ns for e in host if e.name == "bench.window")
        spans = [e for e in host if e.name != "bench.window"]
        pairs = [(a, b) for a in spans for b in spans if a is not b]
        seen["nested"] += any(a.start_ns <= b.start_ns and b.end_ns <= a.end_ns
                              for a, b in pairs)
        seen["partly overlapping"] += any(
            a.start_ns < b.start_ns < a.end_ns < b.end_ns for a, b in pairs)
        gaps = devtrace.device_gaps(devices, lo, hi)
        for s, e in gaps:
            mid = (s + e) / 2
            cover = sorted(h.dur_ns for h in spans
                           if h.start_ns <= mid < h.end_ns)
            if len(cover) > 1 and cover[0] == cover[1]:
                seen["tie"] += 1
                break
        seen["gaps at both edges"] += bool(
            gaps and gaps[0][0] == lo and gaps[-1][1] == hi)
        seen["ops across an edge"] += any(
            e.start_ns < x < e.end_ns for ops in devices.values()
            for e in ops for x in (lo, hi))
        seen["two planes"] += len(devices) == 2
        seen["calls across an edge"] += any(
            c.name == "bench.call" and c.start_ns < x < c.end_ns
            for c in host for x in (lo, hi))
        seen["no gap"] += not gaps
        seen["no summary"] += devtrace.summarize(devices, host) is None
    cases = ("nested", "partly overlapping", "tie", "gaps at both edges",
             "ops across an edge", "two planes", "calls across an edge",
             "no gap")
    assert all(seen[c] >= 2 for c in cases), dict(seen)
    assert seen["no summary"] < 10, dict(seen)


def _rows1_trace(n_calls, ops_per_call=31, seed=0):
    """A traced window of one-row calls, as the chip records them: 2.1 ms
    calls back to back, each with one ``bench.call`` span and 31 short
    device operations, on a clock of absolute nanoseconds."""
    rng = np.random.default_rng(seed)
    t0 = 1.7e9
    call = 2.1e6
    starts = t0 + call * np.arange(n_calls) + rng.uniform(0, 2e3, n_calls)
    # the ops of a call run 1.0-1.9 ms into it, 1-3 us each
    offs = 1.0e6 + np.sort(rng.uniform(0, 0.9e6, (n_calls, ops_per_call)), 1)
    op_s = (starts[:, None] + offs).ravel()
    op_e = op_s + rng.uniform(1e3, 3e3, op_s.size)
    names = [OP_NAMES[i % 3] for i in range(ops_per_call)] * n_calls
    ops = [Event(n, s, e, KERNEL if n == "%kernel.2" else "")
           for n, s, e in zip(names, op_s.tolist(), op_e.tolist())]
    ends = (starts + call - rng.uniform(1e3, 5e3, n_calls)).tolist()
    host = [Event("bench.window", t0, t0 + call * n_calls)]
    host += [Event("bench.call", s, e) for s, e in zip(starts.tolist(), ends)]
    return {"/device:TPU:0": ops}, host


def test_the_rows1_shape_agrees_with_the_naive_reduction():
    devices, host = _rows1_trace(150)
    ours = devtrace.summarize(devices, host)
    _assert_same(ours, _naive_summarize(devices, host))
    assert len(ours.calls) == 150
    assert ours.idle_gaps[0][0] == "bench.call"


def test_a_window_of_twenty_thousand_calls_reduces_in_seconds():
    """20,000 one-row calls of 31 device ops: 620,000 gaps.  Scanning
    every span for every gap would take minutes; the sweeps take
    seconds."""
    devices, host = _rows1_trace(20_000)
    t = time.perf_counter()
    s = devtrace.summarize(devices, host)
    took = time.perf_counter() - t
    assert took < 30.0, took
    assert len(s.calls) == 20_000
    assert s.kernel_events == 20_000 * 10
    assert all(busy > 0 for _, busy in s.calls)
