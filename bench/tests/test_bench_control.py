"""``correct`` comes out false for the control and for each fault a
serving cell can have, at a small size on the CPU: the harness is driven
as in a run, minus its look for a chip, with the timed path broken
underneath."""
import numpy as np
import pytest

from bench import harness
from bench.control import bf16_gathers
from bench.tests import tiny

CELLS = ["tiny_forest.batch4096", "tiny_fleet.rotation"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, seed=11):
    return harness.run_cell(root, cell, seed, 0.5, False,
                            require_tpu=False, compile_cache=False)


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(root, cell):
    with bf16_gathers():
        out = _run(root, cell)
    assert out["correct"] is False
    (number,) = out["checks"].values()
    assert number["value"] > number["limit"]


def _answer_altered(monkeypatch):
    from repro.serving.server import ForestServer

    finalize = ForestServer._finalize

    def altered(self, plan, total):
        out = finalize(self, plan, total)
        out[0] = out[0].copy()
        out[0][0] += 1.0
        return out

    monkeypatch.setattr(ForestServer, "_finalize", altered)


def _half_the_trees(monkeypatch):
    from repro.serving import engines

    run = engines.run_pipelined

    def half(store, plan, pack, xb, interpret=None):
        seg = pack.tree_seg.copy()
        for s in np.unique(seg[seg >= 0]):
            at = np.flatnonzero(seg == s)
            seg[at[len(at) // 2:]] = -1
        return run(store, plan, pack._replace(tree_seg=seg), xb, interpret)

    monkeypatch.setattr(engines, "run_pipelined", half)


@pytest.mark.parametrize("fault", [_answer_altered, _half_the_trees],
                         ids=["answer_altered", "half_the_trees"])
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(root, cell, fault, monkeypatch):
    fault(monkeypatch)
    out = _run(root, cell)
    assert out["correct"] is False


def test_sound_runs_stay_correct_after_the_control(root):
    with bf16_gathers():
        _run(root, CELLS[0])
    assert _run(root, CELLS[0])["correct"] is True


def test_calibrate_reads_program_and_control(root, capsys):
    import json

    from bench import calibrate

    assert calibrate.main([
        "--root", str(root), "--cpu", "--workload", CELLS[0],
        "--seconds", "0.5", "--seeds", "21", "--control-seeds", "22",
    ]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["label"] for x in lines] == ["program", "control"]
    assert lines[0]["numbers"]["wrong_votes"] == 0
    assert lines[1]["numbers"]["wrong_votes"] > 0
