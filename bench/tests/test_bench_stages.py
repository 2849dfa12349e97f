"""The program's stage spans in the trace: their reduction (``stages``),
its agreement with ``devtrace``, and the spans the served call really
records."""
import json

import numpy as np
import pytest

from bench import devtrace, harness, stages
from bench.devtrace import Event
from bench.stages import Span
from bench.tests import tiny


def _sp(name, s, e, line="t0"):
    return Span(name, float(s), float(e), "", line)


def _kernel(s, e):
    return Event("%k.1", float(s), float(e), 'custom_call_target="tpu_custom_call"')


def _call(at, line="t0"):
    """One served call of 100 ns at ``at``: every stage, as the pipelined
    engine nests them."""
    return [
        _sp("bench.call", at, at + 100, line),
        _sp("serve.call", at + 1, at + 99, line),
        _sp("serve.plan", at + 2, at + 10, line),
        _sp("serve.prep", at + 10, at + 20, line),
        _sp("serve.pack", at + 20, at + 25, line),
        _sp("serve.prep", at + 25, at + 30, line),
        _sp("serve.prep", at + 30, at + 32, line),
        _sp("tree_predict.upload", at + 32, at + 40, line),
        _sp("tree_predict.launch", at + 40, at + 50, line),
        _sp("serve.wait", at + 50, at + 90, line),
        _sp("serve.finalize", at + 90, at + 94, line),
        _sp("serve.finalize", at + 94, at + 98, line),
    ]


def test_self_time_on_one_line():
    spans = [_sp("bench.window", 0, 1000)] + _call(100) + _call(300)
    table = stages.span_table(spans, 0, 1000)
    assert table["bench.call"] == (2, pytest.approx(200e-9), pytest.approx(4e-9))
    assert table["serve.call"] == (2, pytest.approx(196e-9), pytest.approx(4e-9))
    assert table["serve.prep"] == (6, pytest.approx(34e-9), pytest.approx(34e-9))
    assert table["serve.wait"][2] == pytest.approx(80e-9)
    assert table["bench.window"][2] == pytest.approx(800e-9)
    assert stages.per_call_ms(table, "serve.finalize") == pytest.approx(8e-6)
    assert stages.per_call_ms(table, "serve.wait") == pytest.approx(40e-6)
    # the stages and serve.call's own time make up serve.call
    own = sum(table[n][2] for n in stages.STAGES)
    assert own + table["serve.call"][2] == pytest.approx(table["serve.call"][1])


def test_self_time_on_two_lines():
    """The scheduler plans on the submitting thread and executes on the
    worker: spans of one line never count as nested in the other's, even
    where their times overlap."""
    spans = [
        _sp("bench.window", 0, 100, "submit"),
        _sp("serve.plan", 10, 30, "submit"),
        _sp("serve.prep", 20, 60, "worker"),
        _sp("serve.wait", 40, 50, "worker"),
        _sp("serve.finalize", 45, 48, "worker"),
    ]
    table = stages.span_table(spans, 0, 100)
    assert table["serve.plan"][2] == pytest.approx(20e-9)
    assert table["serve.prep"][2] == pytest.approx(30e-9)
    assert table["serve.wait"][2] == pytest.approx(7e-9)
    assert table["bench.window"][2] == pytest.approx(80e-9)
    # no bench.call: nothing per call
    assert stages.per_call_ms(table, "serve.plan") is None


def test_overlapping_nested_spans_count_once():
    spans = [_sp("a", 0, 100), _sp("b", 10, 50), _sp("c", 40, 70),
             _sp("d", 45, 60)]
    assert stages.self_times(spans) == [40.0, 40.0, 15.0, 15.0]


def test_spans_outside_the_window_are_left_out():
    spans = [_sp("bench.window", 100, 200), _sp("serve.call", 50, 150),
             _sp("serve.call", 120, 180)]
    assert stages.span_table(spans, 100, 200)["serve.call"][0] == 1


def test_idle_gap_goes_to_the_innermost_stage():
    spans = [_sp("bench.window", 0, 1000)] + _call(100)
    devices = {"/device:TPU:0": [_kernel(145, 185)]}
    gaps = stages.device_gaps(devices, 0, 1000)
    assert gaps == [(0, 145), (185, 1000)]
    idle = stages.idle_by_span(gaps, spans)
    # mid 72.5: before the call; mid 592.5: after it
    assert set(idle) == {"bench.window"}
    devices = {"/device:TPU:0": [_kernel(0, 143), _kernel(145, 185),
                                 _kernel(195, 1000)]}
    gaps = stages.device_gaps(devices, 0, 1000)
    idle = stages.idle_by_span(gaps, spans)
    assert idle == {"tree_predict.launch": pytest.approx(2e-9),
                    "serve.finalize": pytest.approx(10e-9)}


def test_idle_time_splits_across_the_stages_it_overlaps():
    spans = [_sp("bench.window", 0, 1000)] + _call(100)
    gaps = stages.device_gaps({"/device:TPU:0": [_kernel(145, 185)]}, 0, 1000)
    split = stages.idle_in_spans(gaps, spans)
    assert split == {
        "bench.window": pytest.approx(900e-9), "bench.call": pytest.approx(2e-9),
        "serve.call": pytest.approx(2e-9), "serve.plan": pytest.approx(8e-9),
        "serve.prep": pytest.approx(17e-9), "serve.pack": pytest.approx(5e-9),
        "tree_predict.upload": pytest.approx(8e-9),
        "tree_predict.launch": pytest.approx(5e-9),
        "serve.wait": pytest.approx(5e-9), "serve.finalize": pytest.approx(8e-9)}
    # on one line the parts add up to the idle time
    assert sum(split.values()) == pytest.approx(sum(e - s for s, e in gaps) / 1e9)


def _mixed_trace():
    """A window of four calls, one on another line, with device ops
    between and inside them."""
    host = [_sp("bench.window", 0, 1000)]
    for at, line in ((100, "t0"), (300, "t0"), (520, "t1"), (700, "t0")):
        host += _call(at, line)
    devices = {"/device:TPU:0": [
        _kernel(150, 185), Event("fusion.1", 186, 190), _kernel(345, 385),
        Event("copy", 300, 343), Event("copy", 560, 580), _kernel(740, 790),
        Event("fusion.1", 1001, 1010),
    ]}
    return devices, host


def test_the_sweep_puts_gaps_where_devtrace_does():
    devices, host = _mixed_trace()
    lo, hi = stages.window(host)
    ours = stages.idle_by_span(stages.device_gaps(devices, lo, hi), host)
    theirs = devtrace.summarize(devices, host).idle_gaps
    assert ours == pytest.approx(dict(theirs))
    assert ours["tree_predict.launch"] == pytest.approx(2e-9)
    assert ours["serve.wait"] == pytest.approx(1e-9)


def test_program_spans_leave_devtrace_and_its_readers_unchanged():
    """Adding the program's spans to the host events changes nothing the
    accepted metrics read: idle share, kernel time and events, calls and
    device ops, and so every reader's value."""
    devices, host = _mixed_trace()
    bench_only = [s for s in host if s.name.startswith("bench.")]
    before = devtrace.summarize(devices, bench_only)
    after = devtrace.summarize(devices, host)
    for key in ("window_s", "busy_s", "idle_share", "kernel_s",
                "kernel_events", "calls", "device_ops", "n_devices"):
        assert getattr(after, key) == getattr(before, key), key
    root = tiny.REPO
    spec = json.loads((root / "BENCHMARK.json").read_text())
    win = type("W", (), {"calls": [(0.0, 1e-7, [0])] * 4})()
    for m in spec["per_layer"]:
        read = harness.load_reader(root, m["name"])
        values = [read(harness.Context({}, {}, win, s, {}, 1e-9))
                  for s in (before, after)]
        assert values[0] == values[1], m["name"]
        assert values[0] is not None, m["name"]


def test_kernel_in_launch_to_wait():
    devices, host = _mixed_trace()
    # three kernels, each after its call's launch and before its wait ends
    assert stages.kernel_in_call(devices, host) == 1.0
    late = {"/device:TPU:0": [_kernel(150, 195)]}  # ends after the wait
    assert stages.kernel_in_call(late, host) == 0.0
    assert stages.kernel_in_call({}, host) is None


def test_breakdown_of_the_mixed_trace():
    devices, host = _mixed_trace()
    out = stages.breakdown(devices, host)
    assert out["calls"] == 4
    assert out["serve_call_self_share"] == pytest.approx(2 / 98)
    assert out["serve_call_over_bench_call"] == pytest.approx(0.98)
    assert out["kernel_in_launch_to_wait"] == 1.0
    assert out["stages_ms_per_call"]["serve.wait"] == pytest.approx(40e-6)
    assert out["idle_share"] == devtrace.summarize(devices, host).idle_share
    assert stages.breakdown(devices, host[1:]) is None  # no window


def test_nothing_to_read_gives_none():
    assert stages.per_call_ms({}, "serve.plan") is None
    assert stages.per_call_ms({"bench.call": (3, 1.0, 0.1)}, "serve.plan") is None
    assert stages.window([_sp("serve.call", 0, 1)]) is None


def _forest_server():
    from repro.core.forest_codec import compress_forest
    from repro.serving import ForestServer
    from repro.store import make_synthetic_fleet

    (forest,) = make_synthetic_fleet(
        1, n_trees=(6, 6), max_depth=4, d=5, n_bins=8, seed=3
    ).values()
    return ForestServer.from_forest(compress_forest(forest, engine="chunked"))


@pytest.mark.parametrize("engine", ["pipelined", "simple"])
def test_the_served_call_records_every_stage(tmp_path, engine):
    """Served on the CPU under the profiler: each stage appears in every
    call, inside that call's ``serve.call``, which carries ``seq`` and
    ``rows``; the stages' self times and ``serve.call``'s own time add up
    to its duration."""
    import jax
    from jax.profiler import ProfileData

    server = _forest_server()
    rng = np.random.default_rng(0)
    batches = [[("forest", rng.integers(0, 8, (n, 5)).astype(np.int32))]
               for n in (3, 17, 3)]
    server.serve(batches[0], engine=engine)  # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for b in batches:
            server.serve(b, engine=engine)
    finally:
        jax.profiler.stop_trace()
    path = devtrace.find_xplane(tmp_path)
    spans = [s for s in stages.read_spans(path)
             if s.name.startswith(("serve.", "tree_predict."))]
    calls = sorted((s for s in spans if s.name == "serve.call"),
                   key=lambda s: s.start_ns)
    assert len(calls) == 3
    stats = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "serve.call":
                    stats[ev.start_ns] = dict(ev.stats)
    assert [stats[c.start_ns] for c in calls] == [
        {"seq": 2, "rows": 3}, {"seq": 3, "rows": 17}, {"seq": 4, "rows": 3}]
    own = stages.self_times(spans)
    for c in calls:
        inside = [(s, o) for s, o in zip(spans, own)
                  if s is not c and s.line == c.line
                  and c.start_ns <= s.start_ns and s.end_ns <= c.end_ns]
        assert {s.name for s, _ in inside} == set(stages.STAGES)
        c_own = next(o for s, o in zip(spans, own) if s is c)
        assert sum(o for _, o in inside) + c_own == pytest.approx(c.dur_ns)
    # nothing outside a call
    assert all(any(c.start_ns <= s.start_ns and s.end_ns <= c.end_ns
                   for c in calls) for s in spans)


def test_serve_safe_is_one_call():
    """``serve_safe`` serves through the same stages under one
    ``serve.call``; the server's call counter counts it once."""
    server = _forest_server()
    x = np.zeros((2, 5), np.int32)
    out = server.serve_safe([("forest", x)])
    assert out[0].status == "ok"
    assert server.calls == 1
    server.serve([("forest", x)])
    assert server.calls == 2


def test_the_script_reads_a_tiny_cell(tmp_path, capsys):
    root = tiny.make_root(tmp_path)
    out = tmp_path / "lines.jsonl"
    stages.main(["--workload", "tiny_forest.rows1", "--seed", str(2**31 + 9),
                 "--seconds", "0.3", "--windows", "0,1", "--out", str(out)],
                root=root)
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert [x["traced"] for x in lines] == [False, True]
    assert all(x["rows_per_s"] > 0 for x in lines)
    b = lines[1]["breakdown"]
    assert b["calls"] == lines[1]["calls"]
    assert all(b["stages_ms_per_call"][n] > 0 for n in stages.STAGES)
    assert b["serve_call_over_bench_call"] > 0.9
    assert "idle_share" not in b  # the CPU has no device plane
