"""A small copy of the benchmark for the CPU: the repository's metric
readers and traffic files, with the real cells made tiny by shrinking
their configuration, and a tiny regression fleet served in a rotation."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: the real configuration's small twin; depth 7 gives heaps of 255 slots,
#: two rows of the kernel's two-level gather, so the one-hot contraction
#: (which the control lowers to bfloat16) is on the path
CONFIGS = {
    "tiny_forest": ("forests_rf500", {"n_trees": [12, 12], "max_depth": 7,
                                      "n_features": 9, "n_classes": 3}),
}
#: a regression fleet of five users, which BENCHMARK.json has no cell for
#: yet: the harness serves one from its configuration file alone
FLEET = {
    "name": "tiny_fleet", "source": "test", "kind": "fleet",
    "task": "regression", "n_users": 5, "n_trees": [3, 9], "max_depth": 7,
    "n_features": 6, "n_classes": 0, "n_bins": 32, "data_seed": 20181027,
    "limits": {"worst_gap": 1.0},
}
TRAFFIC = {
    "batch4096": {"rows_per_user": 300, "check_calls": 4},
    "rows1": {},
}
ROTATION = {"loop": "closed", "users_per_call": 2, "rows_per_user": 40,
            "check_calls": 16}
CELLS = {
    "tiny_forest.batch4096": ("tiny_forest", "batch4096"),
    "tiny_forest.rows1": ("tiny_forest", "rows1"),
}
FLEET_CELL = "tiny_fleet.rotation"


def make_root(root: Path) -> Path:
    """Write the small benchmark under ``root`` and return it."""
    root = Path(root)
    bench = root / "bench"
    (bench / "configs").mkdir(parents=True, exist_ok=True)
    (bench / "traffic").mkdir(exist_ok=True)
    shutil.copytree(REPO / "bench" / "metrics", bench / "metrics",
                    dirs_exist_ok=True)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    real_cells = {w["name"]: w for w in spec["workloads"]}
    for name, (real, sizes) in CONFIGS.items():
        config = json.loads(
            (REPO / "bench" / "configs" / f"{real}.json").read_text()
        )
        config.update(sizes, name=name)
        (bench / "configs" / f"{name}.json").write_text(json.dumps(config))
    for name, changes in TRAFFIC.items():
        mix = json.loads((REPO / "bench" / "traffic" / f"{name}.json")
                         .read_text())
        mix.update(changes)
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    spec["configs"] = [
        {"name": n, "source": "small twin for the tests",
         "file": f"bench/configs/{n}.json", "reduced": [], "why": "tests"}
        for n in CONFIGS
    ]
    rename = {}
    spec["workloads"] = []
    for name, (config, mix) in CELLS.items():
        real = f"{CONFIGS[config][0]}.{mix}"
        rename[real] = name
        spec["workloads"].append(dict(real_cells[real], name=name,
                                      config=config))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    (bench / "configs" / "tiny_fleet.json").write_text(json.dumps(FLEET))
    (bench / "traffic" / "rotation.json").write_text(json.dumps(ROTATION))
    spec["configs"].append({"name": "tiny_fleet", "source": "test",
                            "file": "bench/configs/tiny_fleet.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": FLEET_CELL, "config": "tiny_fleet",
                              "traffic": "rotation", "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m and m.get("moves", m["name"]) == "rows_per_s":
            m["workloads"].append(FLEET_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    return root
