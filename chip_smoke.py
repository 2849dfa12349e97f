"""Smoke run of the served path on a TPU: compressed forests served through
``ForestServer`` on the chip, every answer checked against the references.

    python chip_smoke.py             # phases ``single`` and ``fleet``, one chip
    python chip_smoke.py --chips 4   # phase ``sharded``: the fleet over four
                                     # chips, against the one-chip answer

Phases (data made from ``--seed``; nothing is read from outside the repo):

* ``single`` — the paper's subscriber device scoring from the compressed
  format: one forest of 500 trees, depth 10, 55 features, 7 classes (the
  ``forests`` row of ``data/tabular.py``), 32 bins, served through
  ``ForestServer.from_forest`` in batches of 1, 256 and 4096 rows.
* ``fleet`` — the multi-tenant store: 128 users with 16 to 48 trees each,
  depth 8, 32 features, regression (the ``liberty_reg`` shape), built with
  ``build_store``; ragged batches of 64 requests of 16 to 256 rows each go
  through ``plan``/``execute``.
* ``sharded`` (``--chips 4`` only) — the ``fleet`` phase with the engine
  forced to ``sharded`` over a 4-device mesh, compared with the one-chip
  ``pipelined`` answer and with ``predict_compressed``; then the same for
  a classification fleet (7 classes, depth 8), which the kernel's
  ``gemm`` body serves.

Every prediction is compared with ``predict_compressed`` and with a plain
numpy walk of the uncompressed forest: votes must be equal, regression
means may differ by the float32 summation bound of ``serving.parity``.
Any mismatch, exception or failed check exits nonzero; the last line of a
passing run is ``{"ok": true, "device": {...}}``.  The times printed are
one smoke run's readings, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from collections import Counter
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SINGLE = {"trees": 500, "depth": 10, "classes": 7, "bins": 32,
          "batches": (1, 256, 4096)}
FLEET = {"users": 128, "trees": (16, 48), "depth": 8, "bins": 32,
         "n_batches": 3, "requests": 64, "rows": (16, 256)}
CLASS_FLEET = dict(FLEET, task="classification", classes=7)


def forest_reference(forest, xb: np.ndarray) -> np.ndarray:
    """(N,) majority vote or mean leaf fit of an uncompressed ``Forest``,
    walking each tree's node arrays with numpy."""
    n = len(xb)
    rows = np.arange(n)
    classify = forest.meta.task == "classification"
    votes = np.zeros((n, max(forest.meta.n_classes, 1)), np.int64)
    acc = np.zeros(n, np.float64)
    for tree in forest.trees:
        idx = np.zeros(n, np.int64)
        while True:
            feat = tree.feature[idx]
            active = feat >= 0
            if not active.any():
                break
            left = xb[rows, np.maximum(feat, 0)] <= tree.threshold[idx]
            nxt = np.where(
                left, tree.children_left[idx], tree.children_right[idx]
            )
            idx = np.where(active, nxt, idx)
        leaf = tree.node_fit[idx].astype(np.int64)
        if classify:
            votes[rows, leaf] += 1
        else:
            acc += forest.fit_values[leaf]
    if classify:
        return votes.argmax(1).astype(np.float64)
    return acc / max(forest.n_trees, 1)


def on_host():
    """Context that runs JAX work on the CPU backend when there is one:
    the references then stay off the chip under test, and each of their
    many small shapes compiles in a fraction of the time."""
    import contextlib

    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def check(name: str, pred, refs: dict, tol: float) -> None:
    """Raise unless ``pred`` is within ``tol`` of every reference."""
    from repro.serving.parity import count_mismatches

    for ref_name, ref in refs.items():
        bad = count_mismatches(pred, ref, tol)
        if bad:
            raise AssertionError(
                f"{name}: {bad} rows disagree with {ref_name} (tol {tol})"
            )


def binned_rows(spec_name: str, bins: int, seed: int) -> np.ndarray:
    """The Table-2 dataset of ``spec_name``, binned to ``bins`` bins."""
    from repro.data.tabular import make_dataset, spec_by_name
    from repro.forest import fit_binner

    x, _, cat = make_dataset(spec_by_name(spec_name), seed=seed)
    return fit_binner(x, n_bins=bins, categorical=cat).transform(x)


def server_report(server) -> dict:
    """Engine usage, arena bytes and health counters of one session."""
    stats = server.stats()
    arena = stats["arena"]
    return {
        "engine_counts": stats["engine_counts"],
        "arena_bytes": 2 * 4 * arena["buffer_trees"] * arena["heap_width"],
        "degraded_batches": stats["health"]["degraded_batches"],
        "interpreted_batches": stats["health"]["interpreted_batches"],
    }


def assert_served_on(server, engine: str) -> None:
    """Only ``engine`` ran, no batch degraded to another, and no kernel ran
    in interpret mode unless the server was built to."""
    rep = server_report(server)
    if set(rep["engine_counts"]) != {engine}:
        raise AssertionError(f"engines ran: {rep['engine_counts']}")
    if rep["degraded_batches"]:
        raise AssertionError(f"{rep['degraded_batches']} degraded batches")
    if rep["interpreted_batches"] and not server.interpret:
        raise AssertionError(
            f"{rep['interpreted_batches']} batches ran in interpret mode"
        )


def peak_bytes() -> int | None:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_single(seed: int, sizes: dict = SINGLE,
                 interpret: bool | None = None):
    from repro.core.compressed_predict import predict_compressed
    from repro.core.forest_codec import CompressedForest, compress_forest
    from repro.serving import ForestServer
    from repro.store import make_synthetic_fleet

    t0 = time.perf_counter()
    forest = make_synthetic_fleet(
        1, task="classification", n_trees=(sizes["trees"],) * 2, d=55,
        n_bins=sizes["bins"], max_depth=sizes["depth"],
        n_classes=sizes["classes"], seed=seed,
    )["user00000"]
    # the numpy clustering engine: the dense one compiles a program per
    # model-set shape, hundreds for one forest
    blob = compress_forest(forest, engine="chunked").to_bytes()
    comp = CompressedForest.from_bytes(blob)
    server = ForestServer.from_forest(
        comp, n_devices=1, interpret=interpret
    )
    xb = binned_rows("forests", sizes["bins"], seed)
    out = {"phase": "single", "trees": forest.n_trees,
           "depth": forest.max_depth(), "compressed_bytes": len(blob),
           "build_s": time.perf_counter() - t0, "batches": []}
    rng = np.random.default_rng(seed)
    for n in sizes["batches"]:
        x = xb[rng.choice(len(xb), n, replace=n > len(xb))]
        requests = [("forest", x)]
        plan = server.plan(requests)
        if plan.engine.name != "pipelined":
            raise AssertionError(f"single: plan chose {plan.engine}")
        t0 = time.perf_counter()
        (pred,) = server.execute(plan, [x])
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        (warm,) = server.serve(requests)
        warm_s = time.perf_counter() - t0
        with on_host():
            ref = predict_compressed(comp, x)
        refs = {"predict_compressed": ref,
                "uncompressed forest": forest_reference(forest, x)}
        check(f"single/{n} rows", pred, refs, 0.0)
        check(f"single/{n} rows (warm)", warm, refs, 0.0)
        out["batches"].append(
            {"rows": n, "first_s": first, "warm_s": warm_s}
        )
    assert_served_on(server, "pipelined")
    out.update(server_report(server))
    out["peak_bytes_in_use"] = peak_bytes()
    return out


def fleet_batches(store, xb: np.ndarray, sizes: dict, seed: int):
    """``n_batches`` ragged batches of ``requests`` (user, rows) pairs."""
    rng = np.random.default_rng(seed)
    users = store.user_ids
    lo, hi = sizes["rows"]
    return [
        [
            (users[int(rng.integers(len(users)))],
             xb[rng.choice(len(xb), int(rng.integers(lo, hi + 1)))])
            for _ in range(sizes["requests"])
        ]
        for _ in range(sizes["n_batches"])
    ]


def build_fleet(seed: int, sizes: dict):
    from repro.store import build_store, make_synthetic_fleet

    t0 = time.perf_counter()
    fleet = make_synthetic_fleet(
        sizes["users"], task=sizes.get("task", "regression"),
        n_trees=sizes["trees"], d=32, n_bins=sizes["bins"],
        max_depth=sizes["depth"], n_classes=sizes.get("classes", 2),
        seed=seed,
    )
    store = build_store(fleet, seed=seed)
    build_s = time.perf_counter() - t0
    xb = binned_rows("liberty_reg", sizes["bins"], seed)
    return fleet, store, fleet_batches(store, xb, sizes, seed + 1), build_s


def kernel_body(store, plan, sizes: dict) -> str:
    """The pipelined kernel's traversal body for ``plan``'s shapes."""
    from repro.kernels.tree_predict.tree_predict import select_path

    return select_path(
        sizes["depth"], sizes.get("classes", 0), store.arena.tb2, 32,
        plan.engine.block_trees, min(plan.engine.block_obs, plan.n_rows),
    )


def fleet_task(fleet) -> str:
    return next(iter(fleet.values())).meta.task


def by_user(batches, preds_by_batch) -> dict[str, np.ndarray]:
    """Each user's predictions over all its requests in ``batches``,
    concatenated in request order."""
    parts: dict[str, list] = {}
    for batch, preds in zip(batches, preds_by_batch):
        for (u, _), p in zip(batch, preds):
            parts.setdefault(u, []).append(p)
    return {u: np.concatenate(ps) for u, ps in parts.items()}


def user_references(store, fleet, batches) -> dict[str, dict]:
    """``predict_compressed`` and the uncompressed forest for every user
    over all of its rows in ``batches``: one reference call per user."""
    rows = by_user(batches, [[x for _, x in batch] for batch in batches])
    with on_host():
        compressed = {u: store.predict(u, x) for u, x in rows.items()}
    return {
        "predict_compressed": compressed,
        "uncompressed forest": {
            u: forest_reference(fleet[u], x) for u, x in rows.items()
        },
    }


def check_fleet(name, fleet, served: dict, refs: dict, tol_scale=1.0):
    """Compare every user's served predictions with each reference."""
    from repro.serving.parity import served_tolerance

    # votes must be equal; a regression mean may sit T * 2**-24 * max|fit|
    # from the exact one, because the device sums the T leaf fits in
    # float32 (serving.parity derives the bound)
    for u, pred in served.items():
        check(f"{name}/{u}", pred, {k: v[u] for k, v in refs.items()},
              tol_scale * served_tolerance(fleet[u]))


def serve_batches(server, batches, engine=None, name="fleet"):
    """plan + cold execute + warm execute per batch; returns the warm
    predictions and per-batch times."""
    preds, times = [], []
    for batch in batches:
        plan = server.plan(batch, engine=engine)
        want = engine or "pipelined"
        if plan.engine.name != want:
            raise AssertionError(f"{name}: plan chose {plan.engine}")
        xs = [x for _, x in batch]
        t0 = time.perf_counter()
        server.execute(plan, xs)
        first = time.perf_counter() - t0
        t0 = time.perf_counter()
        preds.append(server.execute(plan, xs))
        times.append({"rows": plan.n_rows, "trees": plan.t_pad,
                      "first_s": first,
                      "warm_s": time.perf_counter() - t0})
    return preds, times


def phase_fleet(seed: int, sizes: dict = FLEET,
                interpret: bool | None = None):
    from repro.serving import ForestServer

    fleet, store, batches, build_s = build_fleet(seed, sizes)
    server = ForestServer(store, n_devices=1, interpret=interpret)
    preds, times = serve_batches(server, batches)
    check_fleet("fleet", fleet, by_user(batches, preds),
                user_references(store, fleet, batches))
    assert_served_on(server, "pipelined")
    out = {"phase": "fleet", "users": len(fleet), "build_s": build_s,
           "batches": times}
    out.update(server_report(server))
    out["peak_bytes_in_use"] = peak_bytes()
    return out


def phase_sharded(seed: int, n_devices: int, sizes: dict = FLEET,
                  interpret: bool | None = None):
    from repro.serving import ForestServer

    fleet, store, batches, build_s = build_fleet(seed, sizes)
    sharded = ForestServer(store, n_devices=n_devices, interpret=interpret)
    preds, times = serve_batches(sharded, batches, engine="sharded",
                                 name="sharded")
    plan = sharded.plan(batches[0], engine="sharded")
    if plan.engine.n_devices != n_devices:
        raise AssertionError(f"sharded over {plan.engine.n_devices} devices")
    path = kernel_body(store, plan, sizes)
    if path != ("gemm" if "classes" in sizes else "walk"):
        raise AssertionError(f"sharded: the kernel ran its {path} body")
    single = ForestServer(store, n_devices=1, interpret=interpret)
    one_chip, one_times = serve_batches(single, batches, name="one chip")
    refs = user_references(store, fleet, batches)
    served, one_chip = by_user(batches, preds), by_user(batches, one_chip)
    check_fleet("sharded", fleet, served, refs)
    check_fleet("one chip", fleet, one_chip, refs)
    # both sit within the bound of the exact mean, so within twice it of
    # each other (the psum adds the shards' partial sums in another order)
    check_fleet("sharded", fleet, served, {"one chip": one_chip},
                tol_scale=2.0)
    assert_served_on(sharded, "sharded")
    assert_served_on(single, "pipelined")
    out = {"phase": "sharded", "task": fleet_task(fleet),
           "path": path, "devices": n_devices, "users": len(fleet),
           "build_s": build_s,
           "batches": times, "one_chip": one_times}
    out.update(server_report(sharded))
    out["peak_bytes_in_use"] = peak_bytes()
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    if len(jax.devices()) < args.chips:
        sys.exit(f"chip_smoke: needs {args.chips} chips, "
                 f"found {len(jax.devices())}")

    from repro.runtime.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    events: Counter[str] = Counter()  # JAX's own monitoring events
    jax.monitoring.register_event_listener(
        lambda event, **_: events.update((event,))
    )
    label = f"one smoke run on {dev.device_kind}, not a benchmark"
    if args.chips == 4:
        phases = [lambda: phase_sharded(args.seed, 4),
                  lambda: phase_sharded(args.seed, 4, CLASS_FLEET)]
    else:
        phases = [lambda: phase_single(args.seed),
                  lambda: phase_fleet(args.seed)]
    for phase in phases:
        result = phase()
        result["label"] = label
        print(json.dumps(result), flush=True)
    print(json.dumps({
        "compile_cache": cache_dir,
        "hits": events["/jax/compilation_cache/cache_hits"],
        "misses": events["/jax/compilation_cache/cache_misses"],
    }), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))


if __name__ == "__main__":
    main()
