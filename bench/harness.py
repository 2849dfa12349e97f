"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

``run_cell`` finds everything by name: the cell in ``BENCHMARK.json``, its
configuration's file, ``bench/traffic/<traffic>.json`` and one reader per
per-layer metric under ``bench/metrics/``.  Adding a cell, a configuration,
a mix or a metric adds files and entries; this module does not change.

Set-up (``setup_s``) runs from the first line of the entry point to the
opening of the window: importing JAX and reaching the chip, loading the
compressed store, uploading it into the device arena, and warming every
shape the window will use.  The first run of a configuration in a
checkout also builds its forests (``forests.ensure_built``); a deployed
server never does that, so those seconds are left out of ``setup_s`` and
printed apart as ``build_s``.  After the warm-up the persistent
compilation cache is switched off, so a compile inside the window (there
should be none) is a full compile in every run, whatever earlier runs of
the checkout left in the cache; such compiles are counted and timed.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from . import devtrace, forests, loops, reference, work
from . import traffic as tr

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HITS = "/jax/compilation_cache/cache_hits"
CACHE_MISSES = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its configuration,
    traffic and metrics."""
    root = Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text()
    )
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _applies(m, name, reported)]
    return Cell(name, int(cell["chips"]), config, traffic, e2e, layer)


def load_reader(root: Path, metric: str):
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = Path(root) / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileLog:
    """JAX's own compile events, stamped on the host clock."""

    def __init__(self) -> None:
        self.compiles: list[tuple[float, float, str]] = []
        self.hits = 0
        self.misses = 0

    def on_event(self, event: str, **_) -> None:
        if event == CACHE_HITS:
            self.hits += 1
        elif event == CACHE_MISSES:
            self.misses += 1

    def on_duration(self, event: str, secs: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.compiles.append(
                (time.perf_counter(), float(secs), str(kw.get("fun_name")))
            )

    def __enter__(self) -> "CompileLog":
        import jax

        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)
        return self

    def __exit__(self, *exc) -> None:
        import jax

        jax.monitoring.unregister_event_listener(self.on_event)
        jax.monitoring.unregister_event_duration_listener(self.on_duration)

    def between(self, t0: float, t1: float) -> tuple[int, float]:
        hit = [s for t, s, _ in self.compiles if t0 <= t <= t1]
        return len(hit), float(sum(hit))


def use_compile_cache(path: Path) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, for
    every compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def stop_compile_cache() -> None:
    """No persistent cache from here on: a compile is a full compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def make_loop(traffic: dict, server, users: list, rows: tr.RowSource):
    """The load loop the traffic file names."""
    if traffic["loop"] == "closed":
        return loops.ClosedLoop(traffic, server, users, rows)
    raise ValueError(f"unknown loop kind {traffic['loop']!r}")


def _plan_counts(server) -> tuple[int, int]:
    s = server.plan_cache.stats()
    return s["plan_hits"], s["plan_misses"]


@dataclass
class Context:
    """What a per-layer reader gets."""

    config: dict
    traffic: dict
    window: loops.Window
    trace: devtrace.Summary | None
    counters: dict
    bound_s_per_call: float | None  # work.py's lower bound, mean per call


def check(forests_ref: reference.Forests, win: loops.Window,
          rows: tr.RowSource, traffic: dict, seed: int, config: dict,
          peaks: dict | None) -> tuple[dict, int, float | None]:
    """Compare the window's answers with the reference.

    Compares ``check_calls`` calls drawn from the seed (0: every call).
    Returns the compared numbers (``name -> value``), the rows compared,
    and work.py's lower-bound seconds per checked call (with ``peaks``
    only)."""
    wrong, gap, compared = 0, 0.0, 0
    bounds = []
    n = int(traffic.get("check_calls", 0)) or len(win.calls)
    rng = np.random.default_rng([seed, 3])
    picked = sorted(rng.choice(len(win.calls), min(n, len(win.calls)),
                               replace=False))
    for members in (win.calls[i][2] for i in picked):
        by_user: dict[str, list[int]] = {}
        for i in members:
            by_user.setdefault(win.requests[i].user, []).append(i)
        call_work = reference.Work()
        fit_values = 0
        for user, idx in by_user.items():
            reqs = [win.requests[i] for i in idx]
            x = np.concatenate([rows.rows_at(r.row_start, r.n_rows)
                                for r in reqs])
            ref, w = forests_ref.walk(user, x)
            call_work += w
            fit_values += len(forests_ref.fits(user))
            at = 0
            for r in reqs:
                bad, g = reference.compare(forests_ref, user, r.answer,
                                           ref[at:at + r.n_rows])
                wrong += bad
                gap = max(gap, g)
                at += r.n_rows
                compared += r.n_rows
        if peaks is not None:
            classify = config["task"] == "classification"
            n_rows = sum(win.requests[i].n_rows for i in members)
            bounds.append(work.lower_bound(
                internal=call_work.internal, leaves=call_work.leaves,
                visits=call_work.visits, n_rows=n_rows,
                n_features=int(config["n_features"]),
                n_bins=int(config["n_bins"]),
                n_leaf_values=(int(config["n_classes"]) if classify
                               else max(len(forests_ref.fits(u))
                                        for u in by_user)),
                table_values=0 if classify else fit_values,
                answer_bytes=1 if classify else 4, peaks=peaks,
            ))
    if config["task"] == "classification":
        numbers = {"wrong_votes": float(wrong)}
    else:
        numbers = {"worst_gap": gap}
    per_call = (float(np.mean([b.seconds for b in bounds]))
                if bounds else None)
    if bounds:
        binds = {b.binds for b in bounds}
        print(f"bench: work lower bound per call {per_call:.6e} s, bound by "
              f"{'/'.join(sorted(binds))}, mean bytes "
              f"{np.mean([b.bytes for b in bounds]):.0f}, mean node visits "
              f"{np.mean([b.ops for b in bounds]):.0f}", file=sys.stderr)
    return numbers, compared, per_call


def _e2e(name: str, win: loops.Window, setup_s: float) -> float:
    if name == "setup_s":
        return setup_s
    if name == "rows_per_s":
        return win.rows / win.seconds
    raise KeyError(f"no end-to-end metric {name!r} in the harness")


def run_cell(root: Path, workload: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True,
             compile_cache: bool = True,
             t_start: float | None = None) -> dict:
    """One run of ``workload``; returns the result line as a dict (its
    ``checks`` last).  Raises ``NoChip`` before any work where the chip
    is missing and ``require_tpu`` is set."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = Path(root)
    cell = load_cell(root, workload)
    import jax

    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {dev.platform!r}")
        if len(devices) < cell.chips:
            raise NoChip(f"needs {cell.chips} chips; found {len(devices)}")
    peaks = work.load_peaks(dev.device_kind) if require_tpu else None
    if compile_cache:
        use_compile_cache(root / "bench" / ".cache" / "jax")
    log = CompileLog()
    with log:
        return _run(root, cell, seed, seconds, trace, peaks, log, t_start,
                    require_tpu, compile_cache)


def _run(root, cell, seed, seconds, trace, peaks, log, t_start,
         require_tpu, compile_cache) -> dict:
    import jax

    config, traffic = cell.config, cell.traffic
    parts = {}
    path, parts["build_s"] = forests.ensure_built(root, config)
    t = time.perf_counter()
    server, users = forests.load_server(config, path)
    parts["load_s"] = time.perf_counter() - t
    t = time.perf_counter()
    forests.upload(server, users)
    parts["upload_s"] = time.perf_counter() - t
    t = time.perf_counter()
    rows = tr.RowSource(seed, int(config["n_features"]), int(config["n_bins"]))
    loop = make_loop(traffic, server, users, rows)
    loop.warm()
    parts["warm_s"] = time.perf_counter() - t
    # set-up's objects leave the collector's reach: a full collection in
    # the window then scans what the window made, as in a long-running
    # server, not the whole load
    gc.collect()
    gc.freeze()
    if compile_cache:
        stop_compile_cache()
    setup_compiles = (len(log.compiles), log.hits, log.misses)
    before = _plan_counts(server)
    trace_dir = root / "bench" / ".cache" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host spans and device ops only: the Python tracer would record
        # every function call and slow the host path it measures
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    setup_s = time.perf_counter() - t_start - parts["build_s"]
    try:
        win = loop.run(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    gc.unfreeze()
    after = _plan_counts(server)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    sstats = server.stats()
    health = sstats["health"]
    n_in, s_in = log.between(win.start, win.end)
    # what the window did to the program's counters, for per-layer readers
    counters = {
        "plan_hits": after[0] - before[0],
        "plan_misses": after[1] - before[1],
        "compile_window_s": s_in, "compile_window_n": n_in,
    }
    info = {
        "setup": {"total_s": setup_s, **parts},
        "compiles_setup": {"backend_compiles": setup_compiles[0],
                           "cache_hits": setup_compiles[1],
                           "cache_misses": setup_compiles[2]},
        "window_counters": counters,
        "engine_counts": sstats["engine_counts"],
        "interpreted_batches": health["interpreted_batches"],
        "degraded_batches": health["degraded_batches"],
        "peak_bytes_in_use": peak,
        "arena": sstats["arena"],
        "window": {"seconds": win.seconds, "requests": len(win.requests),
                   "rows": win.rows},
    }
    for key, value in info.items():
        print(f"bench: {key} {json.dumps(value, default=str)}",
              file=sys.stderr)
    if require_tpu and health["interpreted_batches"]:
        raise RuntimeError(
            f"{health['interpreted_batches']} batches ran in interpret mode "
            "on the chip"
        )
    summary = None
    if trace:
        xplane = devtrace.find_xplane(trace_dir)
        if xplane is not None:
            summary = devtrace.summarize(*devtrace.read_xplane(xplane))
        shutil.rmtree(trace_dir, ignore_errors=True)
    # the program's state goes before the reference runs
    del loop, server
    gc.collect()
    ref = reference.Forests.load(path / "forests.npz")
    numbers, compared, per_call = check(
        ref, win, rows, traffic, seed, config, peaks if trace else None
    )
    limits = config["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = sum(r.status != "ok" for r in win.requests)
    metrics = {}
    if trace:
        ctx = Context(config, traffic, win, summary, counters, per_call)
        for m in cell.per_layer:
            value = load_reader(root, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(_e2e(m["name"], win, setup_s)),
                                  "unit": m["unit"]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(win.requests),
              "failed": int(failed), "metrics": metrics, "device": device,
              "rows_compared": compared}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["checks"] = checks
    return result

