"""The numpy reference against the program's own decode-side oracle
(``predict_compressed``) at a small size, and the comparison's rules."""
import numpy as np
import pytest

from bench import reference
from bench.forests import forest_arrays
from bench.reference import Forests


def _fleet(task, seed):
    from repro.store import make_synthetic_fleet

    return make_synthetic_fleet(
        3, task=task, n_trees=(4, 9), d=7, n_bins=16, max_depth=6,
        n_classes=4, seed=seed,
    )


@pytest.mark.parametrize("task", ["classification", "regression"])
def test_reference_matches_predict_compressed(task):
    from repro.core.compressed_predict import predict_compressed
    from repro.core.forest_codec import compress_forest

    fleet = _fleet(task, seed=5)
    config = {"task": task, "n_classes": 4 if task == "classification" else 0}
    ref = Forests(forest_arrays(config, fleet))
    rng = np.random.default_rng(0)
    x = rng.integers(0, 16, (300, 7)).astype(np.int32)
    for user, forest in fleet.items():
        ours, work = ref.walk(user, x)
        theirs = np.asarray(predict_compressed(
            compress_forest(forest, engine="chunked"), x), np.float64)
        if task == "classification":
            np.testing.assert_array_equal(ours, theirs)
        else:
            np.testing.assert_allclose(ours, theirs, rtol=0, atol=1e-12)
        assert work.visits >= forest.n_trees * len(x)
        assert 0 < work.internal + work.leaves <= sum(
            t.n_nodes for t in forest.trees)


def test_reference_walks_row_blocks_like_one(monkeypatch):
    fleet = _fleet("classification", seed=6)
    ref = Forests(forest_arrays({"task": "classification", "n_classes": 4},
                                fleet))
    x = np.random.default_rng(1).integers(0, 16, (100, 7)).astype(np.int32)
    whole, w1 = ref.walk("user00000", x)
    monkeypatch.setattr(reference, "ROW_BLOCK", 7)
    blocks, w2 = ref.walk("user00000", x)
    np.testing.assert_array_equal(whole, blocks)
    assert (w1.internal, w1.leaves, w1.visits) == \
        (w2.internal, w2.leaves, w2.visits)


def test_compare_counts_missing_and_wrong_answers():
    fleet = _fleet("regression", seed=7)
    ref = Forests(forest_arrays({"task": "regression", "n_classes": 0},
                                fleet))
    good = np.array([0.5, -0.25, 1.0])
    tol = ref.tolerance("user00000")
    assert tol > 0
    assert reference.compare(ref, "user00000", good, good) == (0, 0.0)
    wrong, gap = reference.compare(ref, "user00000", good + 0.5 * tol, good)
    assert wrong == 0 and gap == pytest.approx(0.5)
    wrong, gap = reference.compare(ref, "user00000", good + 3 * tol, good)
    assert wrong == 3 and gap == pytest.approx(3.0)
    assert reference.compare(ref, "user00000", None, good) == \
        (3, reference.MISSING_GAP)
    assert reference.compare(ref, "user00000", good[:2], good) == \
        (3, reference.MISSING_GAP)
