"""The reduction from a profiler trace to the per-layer numbers."""
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
