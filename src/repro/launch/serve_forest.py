"""Single-forest serving benchmark driver.

Serving goes through the unified session API (ISSUE 4):

    from repro.serving import ForestServer
    server = ForestServer.from_forest(comp)
    pred = server.predict(x_binned)

(The PR 1 ``serve_compressed_forest`` shim that bridged callers to this
API has been removed — its deprecation window closed.)  The heap packing
helpers (``tree_to_heap`` / ``iter_heap_tiles``) moved to
``repro.serving.pack`` and are re-exported here for compatibility.

    PYTHONPATH=src python -m repro.launch.serve_forest --trees 100 \
        --depth 8 --rows 5000 --batch 1024
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from ..core.compressed_predict import predict_compressed
from ..core.forest_codec import CompressedForest
from ..serving.pack import iter_heap_tiles, tree_to_heap  # noqa: F401

__all__ = ["iter_heap_tiles", "tree_to_heap"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--rows", type=int, default=5000)
    ap.add_argument("--features", type=int, default=8)
    ap.add_argument("--task", choices=("classification", "regression"),
                    default="classification")
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--block-trees", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from ..core.forest_codec import compress_forest
    from ..data.tabular import TabularSpec, make_dataset
    from ..forest import fit_binner, to_compact_forest, train_forest
    from ..runtime.compile_cache import enable_compile_cache
    from ..serving import ForestServer
    from ..serving.parity import count_mismatches, served_tolerance

    enable_compile_cache()
    spec = TabularSpec("serve", args.rows, args.features, args.task, 2, 2)
    x, y, cat = make_dataset(spec, seed=args.seed)
    binner = fit_binner(x, categorical=cat, n_bins=32)
    model = train_forest(
        x, y, binner, n_trees=args.trees, max_depth=args.depth,
        task=args.task, n_classes=2, seed=args.seed,
    )
    forest = to_compact_forest(model)
    comp = CompressedForest.from_bytes(compress_forest(forest).to_bytes())
    blob_bytes = len(comp.to_bytes())
    xb = binner.transform(x)

    server = ForestServer.from_forest(comp)
    # warm up (jit compile + arena admission) then measure session serving
    server.predict(xb[: args.batch], block_trees=args.block_trees)
    t0 = time.time()
    pred = server.predict(xb[: args.batch], block_trees=args.block_trees)
    t_serve = time.time() - t0
    ref = predict_compressed(comp, xb[: args.batch])
    mismatch = count_mismatches(pred, ref, served_tolerance(comp))
    agree = float((pred == ref).mean()) if args.task == "classification" \
        else float(np.max(np.abs(pred - ref)))
    print(
        f"forest: {args.trees} trees depth {args.depth} "
        f"({blob_bytes} compressed bytes)\n"
        f"serve {args.batch} rows: {t_serve * 1e3:.1f} ms "
        f"({args.batch / t_serve:.0f} rows/s), "
        f"agreement vs predict_compressed: {agree} "
        f"({mismatch} rows outside tolerance)\n"
        f"session: {server.stats()['plan_cache']}"
    )
    if mismatch:
        raise SystemExit(f"{mismatch} rows disagree with predict_compressed")


if __name__ == "__main__":
    main()
