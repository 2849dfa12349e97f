"""Pipelined multi-tenant serving benchmark (ISSUE 3 tentpole measurement).

On the 100-user synthetic fleet (the PR 2 store-bench config), both tasks:

* end-to-end WARM serving rows/s for the three engines —
  ``simple`` (the PR 2 path: host re-pack + one kernel launch per tree
  chunk, at its shipped block sizes), ``pipelined`` (device tile arena +
  one double-buffered DMA launch), ``sharded`` (tree axis partitioned
  across devices + psum) — and the pipelined/sharded speedups over simple
  (acceptance target: >= 2x);
* overlap efficiency: (pack + kernel + finalize stage times, each measured
  standalone) / end-to-end time.  1.0 means the stages ran back-to-back;
  > 1.0 means the engine overlapped them.  Under interpret mode (CPU) the
  DMA pipeline is emulated serially, so this hovers near 1.0 — the number
  exists to track REAL overlap once the kernel runs on TPU hardware;
* single- vs multi-device scaling: sharded warm rows/s over the first
  1/2/4 devices, in one process (on the CPU, run with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=4``; forced host
  devices share the same physical cores, so CPU numbers validate the
  mechanism, not a speedup);
* parity: every engine's predictions vs per-user ``predict_compressed`` —
  classification must be bit-exact, regression reports the float32
  accumulation max error.

Writes machine-readable results to BENCH_serve_pipeline.json (repo root).

    PYTHONPATH=src python benchmarks/serve_pipeline.py [--quick] [--out P]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np


def best_of(fn, repeats):
    """Best-of-N wall time: the box throttles on shared cores, so the MIN
    is the reproducible number (mean folds in scheduler noise)."""
    best, result = float("inf"), None
    for _ in range(repeats):
        t0 = time.time()
        result = fn()
        best = min(best, time.time() - t0)
    return best, result


_SESSIONS: dict = {}


def _server_for(store):
    """One memoized ForestServer per store, so repeated engine timings
    share the session's plan cache (the warm path being measured)."""
    server = _SESSIONS.get(id(store))
    if server is None:
        from repro.serving import ForestServer

        server = ForestServer(store)
        _SESSIONS[id(store)] = server
    return server


def time_engine(store, requests, engine, repeats):
    server = _server_for(store)
    server.serve(requests, engine=engine)  # compile + warm
    return best_of(
        lambda: server.serve(requests, engine=engine), repeats
    )


def pipelined_stage_times(store, requests, repeats):
    """The pipelined engine's stages measured STANDALONE — the exact same
    helpers `_serve_pipelined` composes (pack = group + arena index-gather
    + chunk ranges, kernel = the one DMA launch blocked to completion,
    finalize = unsort + per-request split).  Stage-sum vs end-to-end is
    the overlap efficiency."""
    import jax

    from repro.launch.serve_store import (
        finalize_pipelined_batch,
        pack_pipelined_batch,
        run_pipelined_kernel,
    )
    from repro.serving import ENGINE_BLOCKS

    block_trees, block_obs = ENGINE_BLOCKS["pipelined"]

    def pack():
        pb = pack_pipelined_batch(store, requests, block_trees, block_obs)
        # the arena index-gather dispatches async device work: block so
        # its cost lands in THIS stage, not the kernel stage's wait
        jax.block_until_ready(pb.code)
        jax.block_until_ready(pb.fit)
        return pb

    pb = pack()

    def kernel():
        return jax.block_until_ready(run_pipelined_kernel(store, pb))

    out = kernel()  # compile

    def finalize():
        return finalize_pipelined_batch(store, requests, pb, out)

    stages = {}
    for name, fn in (("pack", pack), ("kernel", kernel),
                     ("finalize", finalize)):
        stages[name], _ = best_of(fn, repeats)
    return stages


def parity(store, requests, preds, task):
    exact, max_err = 0, 0.0
    for (u, x), p in zip(requests, preds):
        ref = store.predict(u, x)
        if task == "classification":
            exact += int(np.array_equal(p, ref))
        else:
            if len(ref):
                max_err = max(max_err, float(np.max(np.abs(p - ref))))
            exact += int(np.allclose(p, ref, rtol=1e-4, atol=1e-4))
    return exact, max_err


def bench_fleet(task, n_users, n_requests, rows_per_request, repeats,
                seed=0):
    import jax

    from repro.store import (
        build_store,
        make_request_batch,
        make_synthetic_fleet,
    )

    fleet = make_synthetic_fleet(n_users, task=task, seed=seed)
    store = build_store(fleet)
    requests = make_request_batch(
        store, n_requests, rows_per_request, seed + 1
    )
    n_rows = sum(len(x) for _, x in requests)

    engines = {}
    preds_by_engine = {}
    for engine in ("simple", "pipelined", "sharded"):
        t_warm, preds = time_engine(store, requests, engine, repeats)
        exact, max_err = parity(store, requests, preds, task)
        preds_by_engine[engine] = preds
        engines[engine] = {
            "warm_ms": round(t_warm * 1e3, 2),
            "rows_per_s": round(n_rows / t_warm, 1),
            "parity_exact_requests": exact,
            "regression_max_abs_err": max_err,
        }
    base = engines["simple"]["warm_ms"]
    for engine in ("pipelined", "sharded"):
        engines[engine]["speedup_vs_simple"] = round(
            base / engines[engine]["warm_ms"], 2
        )
    agree = {
        e: all(
            np.array_equal(a, b) if task == "classification"
            else np.allclose(a, b, rtol=1e-5, atol=1e-5)
            for a, b in zip(preds_by_engine["simple"], preds_by_engine[e])
        )
        for e in ("pipelined", "sharded")
    }

    stages = pipelined_stage_times(store, requests, repeats)
    stage_sum = sum(stages.values())
    overlap = stage_sum / (engines["pipelined"]["warm_ms"] / 1e3)

    return {
        "task": task,
        "n_users": n_users,
        "total_trees": sum(f.n_trees for f in fleet.values()),
        "n_requests": n_requests,
        "rows_per_request": rows_per_request,
        "n_devices": len(jax.devices()),
        "engines": engines,
        "engines_match_simple": agree,
        "pipelined_stages_ms": {
            k: round(v * 1e3, 2) for k, v in stages.items()
        },
        "overlap_efficiency": round(overlap, 3),
        "arena": store.arena.stats() if store.arena is not None else None,
    }


def device_scaling(args, device_counts):
    """Sharded warm rows/s over the first k of ``jax.devices()`` for each
    k, in THIS process (one process owns the devices).  On the CPU the
    device count comes from ``XLA_FLAGS=
    --xla_force_host_platform_device_count=N`` set on the command line;
    counts above ``len(jax.devices())`` are skipped."""
    import jax

    from repro.serving import ForestServer
    from repro.store import (
        build_store,
        make_request_batch,
        make_synthetic_fleet,
    )

    fleet = make_synthetic_fleet(args.users, task="classification",
                                 seed=0)
    store = build_store(fleet)
    requests = make_request_batch(store, args.requests, args.rows, 1)
    n_rows = sum(len(x) for _, x in requests)
    rows = []
    for n_dev in device_counts:
        if n_dev > len(jax.devices()):
            continue
        server = ForestServer(store, n_devices=n_dev)
        server.serve(requests, engine="sharded")  # compile + warm
        t_warm, _ = best_of(
            lambda: server.serve(requests, engine="sharded"), args.repeats
        )
        rows.append({
            "devices": n_dev,
            "sharded_warm_ms": round(t_warm * 1e3, 2),
            "sharded_rows_per_s": round(n_rows / t_warm, 1),
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tiny fleet for CI smoke runs")
    ap.add_argument("--out", default=None)
    ap.add_argument("--users", type=int, default=100)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rows", type=int, default=128)
    ap.add_argument("--repeats", type=int, default=5)
    args = ap.parse_args()
    if args.quick:
        args.users, args.requests, args.rows, args.repeats = 8, 6, 32, 2
    out_path = pathlib.Path(
        args.out
        or pathlib.Path(__file__).resolve().parent.parent
        / "BENCH_serve_pipeline.json"
    )
    results = {
        "benchmark": "serve_pipeline",
        "quick": bool(args.quick),
        "fleets": [
            bench_fleet(task, args.users, args.requests, args.rows,
                        args.repeats)
            for task in ("classification", "regression")
        ],
    }
    if not args.quick:
        results["device_scaling"] = device_scaling(args, [1, 2, 4])
    out_path.write_text(json.dumps(results, indent=2) + "\n")
    print(json.dumps(results, indent=2))
    print(f"\nwrote {out_path}")


if __name__ == "__main__":
    main()
