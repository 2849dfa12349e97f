"""Whole runs of the harness on the CPU at a small size: every cell, a
fleet served from its configuration file alone, a traced run, a cell added
by dropping in files, and the entry point's refusal to run without a
chip."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.tests import tiny

E2E = {
    "tiny_forest.batch4096": {"rows_per_s", "setup_s"},
    "tiny_forest.rows1": {"rows_per_s", "setup_s"},
    "tiny_fleet.rotation": {"rows_per_s", "setup_s"},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, trace=False, seed=2**31 + 5, seconds=0.5):
    return harness.run_cell(root, cell, seed, seconds, trace,
                            require_tpu=False, compile_cache=False)


@pytest.mark.parametrize("cell", sorted(E2E))
def test_each_cell_runs_and_is_correct(root, cell):
    out = _run(root, cell)
    assert out["correct"] is True
    assert set(out["metrics"]) == E2E[cell]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    json.dumps(out, allow_nan=False)


def test_first_run_builds_outside_setup(tmp_path, capsys):
    """The first run of a configuration in a checkout builds its forests;
    those seconds are printed as ``build_s`` and left out of ``setup_s``,
    and the next run loads what the first one built."""
    import time

    fresh = tiny.make_root(tmp_path)
    setups = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = harness.run_cell(fresh, "tiny_forest.rows1", 7, 0.3, False,
                               require_tpu=False, compile_cache=False,
                               t_start=t0)
        line = next(x for x in capsys.readouterr().err.splitlines()
                    if x.startswith("bench: setup "))
        setup = json.loads(line[len("bench: setup "):])
        assert out["correct"] is True
        assert out["metrics"]["setup_s"]["value"] == setup["total_s"]
        assert setup["total_s"] + setup["build_s"] <= time.perf_counter() - t0
        setups.append(setup)
    assert setups[0]["build_s"] > 0
    assert setups[1]["build_s"] == 0


def test_a_cell_added_by_files_only(root, tmp_path):
    """A new configuration, traffic mix and per-layer metric are new files
    and entries; the harness runs them unchanged."""
    new = tmp_path / "added"
    shutil.copytree(root, new)
    bench = new / "bench"
    config = json.loads((bench / "configs/tiny_forest.json").read_text())
    config.update(name="tiny_forest_b", n_trees=[5, 5], data_seed=77)
    (bench / "configs/tiny_forest_b.json").write_text(json.dumps(config))
    (bench / "traffic/pairs50.json").write_text(json.dumps({
        "loop": "closed", "users_per_call": 2, "rows_per_user": 50, "check_calls": 0}))
    (bench / "metrics/window.calls.py").write_text(
        "def read(ctx):\n    return len(ctx.window.calls)\n")
    spec = json.loads((new / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny_forest_b", "source": "test",
                            "file": "bench/configs/tiny_forest_b.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny_forest_b.pairs50",
                              "config": "tiny_forest_b",
                              "traffic": "pairs50", "chips": 1,
                              "why": "test"})
    rows = next(m for m in spec["end_to_end"] if m["name"] == "rows_per_s")
    rows["workloads"].append("tiny_forest_b.pairs50")
    spec["per_layer"].append({
        "name": "window.calls", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "rows_per_s",
        "workloads": ["tiny_forest_b.pairs50"]})
    (new / "BENCHMARK.json").write_text(json.dumps(spec))
    out = _run(new, "tiny_forest_b.pairs50")
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    traced = _run(new, "tiny_forest_b.pairs50", trace=True)
    assert traced["correct"] is True
    assert traced["metrics"]["window.calls"]["value"] >= 1
    assert traced["metrics"]["window.calls"]["unit"] == "calls"


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "forests_rf500.batch4096", "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_entry_point_refuses_without_a_chip():
    done = _entry(tiny.REPO)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "needs a TPU" in done.stderr


def test_entry_point_refuses_without_the_program(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    done = _entry(tmp_path)
    assert done.returncode != 0
    assert done.stdout == ""
    assert not Path(tmp_path, "bench", ".cache").exists()
