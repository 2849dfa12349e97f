"""work.py's lower bound on a hand-counted tree, and the reference walk
that counts it."""
import math

import numpy as np
import pytest

from bench import work
from bench.reference import Forests

# one tree over 2 features with 4 bins and 3 classes:
#   node 0: x[0] <= 1 ? node 1 : node 2
#   node 1: leaf, class 0
#   node 2: x[1] <= 2 ? node 3 : node 4
#   node 3: leaf, class 1;  node 4: leaf, class 2
TREE = {
    "task": np.asarray("classification"), "n_classes": np.asarray(3),
    "users": np.asarray(["u"]),
    "tree_off": np.asarray([0, 1]), "node_off": np.asarray([0, 5]),
    "feature": np.asarray([0, -1, 1, -1, -1]),
    "threshold": np.asarray([1, -1, 2, -1, -1]),
    "left": np.asarray([1, -1, 3, -1, -1]),
    "right": np.asarray([2, -1, 4, -1, -1]),
    "node_fit": np.asarray([0, 0, 0, 1, 2]),
    "fit_values": np.zeros(0), "fit_off": np.asarray([0, 0]),
}
ROWS = np.asarray([[0, 0], [3, 5], [3, 0]], np.int32)
PEAKS = {"hbm_bytes_per_s": 819e9, "ops_per_s": 393e12}


def test_walk_answers_and_counts_by_hand():
    answers, w = Forests(TREE).walk("u", ROWS)
    # row 0 goes left to class 0; row 1 right then right (5 > 2) to class
    # 2; row 2 right then left to class 1
    assert answers.tolist() == [0.0, 2.0, 1.0]
    # visited: nodes 0, 1, 2, 3, 4 -> internal {0, 2}, leaves {1, 3, 4};
    # visits 2 + 3 + 3 node-steps
    assert (w.internal, w.leaves, w.visits) == (2, 3, 8)


def test_lower_bound_by_hand():
    # internal: flag 1 + feature 1 bit (2 features) + bin 2 bits -> 1 B;
    # leaf: flag 1 + class 2 bits -> 1 B; rows 3 x 2 B; answers 3 x 1 B
    assert work.node_bytes(2, 4, 3) == (1, 1)
    b = work.lower_bound(internal=2, leaves=3, visits=8, n_rows=3,
                         n_features=2, n_bins=4, n_leaf_values=3,
                         table_values=0, answer_bytes=1, peaks=PEAKS)
    assert b.bytes == 2 + 3 + 6 + 3
    assert b.ops == 8
    assert b.binds == "bytes"
    assert b.seconds == pytest.approx(14 / 819e9)


def test_ops_bind_when_bandwidth_is_plenty():
    b = work.lower_bound(internal=2, leaves=3, visits=8, n_rows=3,
                         n_features=2, n_bins=4, n_leaf_values=3,
                         table_values=0, answer_bytes=1,
                         peaks={"hbm_bytes_per_s": 1e30, "ops_per_s": 1.0})
    assert (b.binds, b.seconds) == ("ops", 8.0)


def test_widths_of_the_real_schemas():
    # 55 features, 32 bins, 7 classes: 1 + 6 + 5 bits -> 2 B; leaf 1 + 3
    assert work.node_bytes(55, 32, 7) == (2, 1)
    # a regression leaf is an index into a 24-value table: 1 + 5 bits
    assert work.node_bytes(32, 32, 24) == (2, 1)
    assert work.bits(1) == 1 and work.bits(256) == 8
    assert work.bits(257) == math.ceil(math.log2(257))


def test_peaks_of_an_unknown_device_are_an_error():
    assert work.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.load_peaks("cpu")
