"""A configuration's forests: built once per checkout, then loaded as a
deployed server loads its store.

The first run of a configuration in a checkout generates its forests from
the ``data_seed`` in its file and writes two things under
``bench/.cache/<config>-<digest>/``:

* ``store.bin`` — the compressed bytes, in the store's own serialization
  (``CompressedForest.to_bytes`` for one forest, the ``ForestStore`` RFT1
  frame for a fleet);
* ``forests.npz`` — the uncompressed trees as plain arrays, for the
  reference, which so walks trees that never passed through the codec.

Later runs read ``store.bin`` and build the server from it.  The digest is
of the configuration's sizes and seed (``BUILD_KEYS``), so a change to any
of them builds anew.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np


#: the configuration keys the built forests depend on
BUILD_KEYS = ("kind", "task", "n_users", "n_trees", "max_depth",
              "n_features", "n_classes", "n_bins", "data_seed")


def cache_dir(root: Path, config: dict) -> Path:
    text = json.dumps({k: config[k] for k in BUILD_KEYS},
                      sort_keys=True).encode()
    digest = hashlib.sha1(text).hexdigest()[:12]
    return Path(root) / "bench" / ".cache" / f"{config['name']}-{digest}"


def _generate(config: dict):
    from repro.store import make_synthetic_fleet

    lo, hi = config["n_trees"]
    return make_synthetic_fleet(
        int(config["n_users"]), task=config["task"],
        n_trees=(int(lo), int(hi)), d=int(config["n_features"]),
        n_bins=int(config["n_bins"]), max_depth=int(config["max_depth"]),
        n_classes=max(int(config["n_classes"]), 2),
        seed=int(config["data_seed"]),
    )


def _compress(config: dict, fleet: dict) -> bytes:
    if config["kind"] == "single_forest":
        from repro.core.forest_codec import compress_forest

        (forest,) = fleet.values()
        # the numpy clustering engine: the dense one compiles a program per
        # model-set shape, hundreds for one forest
        return compress_forest(forest, engine="chunked").to_bytes()
    if config["kind"] == "fleet":
        from repro.store import build_store

        return build_store(fleet, seed=int(config["data_seed"])).to_bytes()
    raise ValueError(f"unknown configuration kind {config['kind']!r}")


def forest_arrays(config: dict, fleet: dict) -> dict:
    """Plain arrays of every user's uncompressed trees (``reference.Forests``
    reads them)."""
    users = list(fleet)
    trees = [t for u in users for t in fleet[u].trees]
    tree_off = np.cumsum([0] + [fleet[u].n_trees for u in users])
    node_off = np.cumsum([0] + [t.n_nodes for t in trees])
    fits = [np.asarray(fleet[u].fit_values, np.float64) for u in users]
    cat = (lambda k: np.concatenate([getattr(t, k) for t in trees]))
    return {
        "task": np.asarray(config["task"]),
        "n_classes": np.asarray(int(config["n_classes"])),
        "users": np.asarray(users),
        "tree_off": tree_off.astype(np.int64),
        "node_off": node_off.astype(np.int64),
        "feature": cat("feature").astype(np.int16),
        "threshold": cat("threshold").astype(np.int16),
        "left": cat("children_left").astype(np.int32),
        "right": cat("children_right").astype(np.int32),
        "node_fit": cat("node_fit").astype(np.int16),
        "fit_values": np.concatenate(fits) if fits else np.zeros(0),
        "fit_off": np.cumsum([0] + [len(f) for f in fits]).astype(np.int64),
    }


def ensure_built(root: Path, config: dict) -> tuple[Path, float]:
    """The configuration's cache directory, built if missing; returns the
    seconds spent building (0 when it was there)."""
    out = cache_dir(root, config)
    if (out / "done").exists():
        return out, 0.0
    t0 = time.perf_counter()
    fleet = _generate(config)
    blob = _compress(config, fleet)
    arrays = forest_arrays(config, fleet)
    out.mkdir(parents=True, exist_ok=True)
    tmp = out / "store.bin.tmp"
    tmp.write_bytes(blob)
    os.replace(tmp, out / "store.bin")
    with open(out / "forests.tmp.npz", "wb") as f:
        np.savez_compressed(f, **arrays)
    os.replace(out / "forests.tmp.npz", out / "forests.npz")
    (out / "done").write_text(json.dumps({
        "build_s": time.perf_counter() - t0, "store_bytes": len(blob),
    }))
    return out, time.perf_counter() - t0


def load_server(config: dict, path: Path):
    """``ForestServer`` over the compressed bytes at ``path/store.bin``, on
    one device; returns ``(server, user_ids)``."""
    from repro.serving import ForestServer

    blob = (Path(path) / "store.bin").read_bytes()
    if config["kind"] == "single_forest":
        from repro.core.forest_codec import CompressedForest

        server = ForestServer.from_forest(
            CompressedForest.from_bytes(blob), user_id="user00000",
            n_devices=1,
        )
    else:
        from repro.store import ForestStore

        server = ForestServer(ForestStore.from_bytes(blob), n_devices=1)
    return server, list(server.store.user_ids)


def upload(server, users: list[str]) -> None:
    """Admit every user into the device tile arena at the block size the
    pipelined engine gathers with."""
    from repro.serving.plan import ENGINE_BLOCKS

    server.store.arena_ensure(users, ENGINE_BLOCKS["pipelined"][0])
