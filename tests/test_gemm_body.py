"""The pipelined segmented kernel's two traversal bodies: ``gemm`` (three
MXU contractions per tree chunk) against ``walk`` (the per-level heap
gathers) and the packed reference, bit for bit in interpret mode; the
rule that picks between them; and the ``path`` stat on the launch span."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tree_predict import ops
from repro.kernels.tree_predict import tree_predict as tp
from repro.kernels.tree_predict.ref import (
    forest_predict_agg_segmented_packed_reference,
)

N_BINS = 32
TB2 = 2 * tp.fused_threshold_base(N_BINS - 1)


def _forest(rng, n_trees, depth, d, n_classes, n_bins=N_BINS):
    """Fused heaps whose leaves sit at every depth 0..depth-1 (tree ``t``
    stops one random path at depth ``t % depth``; the root of tree 0 is a
    leaf), with random fields, internal flags included, below each leaf
    and on the bottom level, which no body may read."""
    h = (1 << (depth + 1)) - 1
    n_int = (1 << depth) - 1
    feature = rng.integers(0, d, (n_trees, h))
    threshold = rng.integers(0, n_bins, (n_trees, h))
    inter = rng.random((n_trees, h)) < 0.5
    inter[:, :n_int] = True
    for i in range(1, n_int):  # random early leaves
        inter[:, i] &= rng.random(n_trees) < 0.85
    for t in range(n_trees):
        node = 0
        for _ in range(t % depth):
            node = 2 * node + 1 + int(rng.integers(0, 2))
            inter[t, (node - 1) // 2] = True
        inter[t, node] = False
    fit = rng.integers(0, n_classes, (n_trees, h)).astype(np.float32)
    tb = tp.fused_threshold_base(n_bins - 1)
    code = tp.fuse_node_attrs(feature, threshold, inter, tb)
    return code, fit, inter


def _leaf_depths(inter, depth):
    """Depths of the leaves a walk from the root can stop at."""
    reach = np.zeros_like(inter)
    reach[:, 0] = True
    for i in range((1 << depth) - 1):
        go = reach[:, i] & inter[:, i]
        reach[:, 2 * i + 1] |= go
        reach[:, 2 * i + 2] |= go
    stops = reach & ~inter
    return {
        int(np.floor(np.log2(i + 1)))
        for i in range((1 << depth) - 1) if stops[:, i].any()
    }


CASES = {
    # id: depth, d, n_classes, n_trees, n, n_segs, rows sorted, block_obs,
    # bins (up to 64 the operands are int8, past it bfloat16)
    "d8-leaves-every-depth-ragged-n": (8, 54, 7, 37, 300, 3, True, 128, 32),
    "d8-one-row": (8, 54, 7, 24, 1, 1, True, 128, 32),
    "d8-128-classes": (8, 5, 128, 16, 140, 2, True, 128, 32),
    "d8-256-bins-bf16": (8, 54, 7, 19, 150, 2, True, 128, 256),
    "d8-one-row-bf16": (8, 54, 7, 16, 1, 1, True, 128, 100),
    "d6-loose-ranges": (6, 54, 5, 21, 200, 4, False, 64, 32),
    "d6-one-feature-bf16": (6, 1, 3, 12, 96, 2, True, 32, 128),
    "d3-one-feature-ragged-n": (3, 1, 2, 9, 130, 2, True, 128, 32),
    "d3-one-row": (3, 54, 4, 8, 1, 1, True, 128, 32),
}


@pytest.mark.parametrize("case", list(CASES), ids=list(CASES))
def test_gemm_body_matches_walk_and_reference(case):
    depth, d, n_classes, n_trees, n, n_segs, sort_rows, bo, bins = (
        CASES[case]
    )
    rng = np.random.default_rng(list(CASES).index(case))
    bt = 8
    tb2 = 2 * tp.fused_threshold_base(bins - 1)
    code, fit, inter = _forest(rng, n_trees, depth, d, n_classes, bins)
    assert _leaf_depths(inter, depth) == set(range(depth))
    t_pad = -(-n_trees // bt) * bt
    pad = t_pad - n_trees  # padding trees: segment -1, garbage fields
    tseg = np.sort(rng.integers(0, n_segs, n_trees)).astype(np.int32)
    tseg = np.pad(tseg, (0, pad), constant_values=-1)
    code = np.pad(code, ((0, pad), (0, 0)), constant_values=tb2 + 1)
    fit = np.pad(fit, ((0, pad), (0, 0)), constant_values=1.0)
    oseg = rng.integers(0, n_segs, n).astype(np.int32)
    if sort_rows:
        oseg = np.sort(oseg)
    # bins past TB (and far past it) take the right branch everywhere
    xb = rng.integers(0, bins + 8, (n, d)).astype(np.int32)
    xb[rng.random((n, d)) < 0.05] = 1000
    bo = min(bo, n)
    lo, hi = tp.segment_chunk_ranges(oseg, tseg, bt, bo)
    if not sort_rows:
        assert (hi - lo).max() > 1  # loose ranges: masked chunks run
    assert tp.select_path(depth, n_classes, tb2, d, bt, bo) == "gemm"
    assert (tp._gemm_operand(tb2) == jnp.int8) == (bins <= 64)
    args = [jnp.asarray(a) for a in (xb, oseg, code, fit, tseg, lo, hi)]
    got = {
        path: np.asarray(tp._forest_predict_agg_seg_pipelined_impl(
            *args, depth, n_classes, bt, bo, tb2, True, path=path,
        ))
        for path in ("gemm", "walk")
    }
    ref = np.asarray(forest_predict_agg_segmented_packed_reference(
        args[0], args[1], args[2], args[3], args[4], depth, tb2,
        n_classes=n_classes,
    ))
    assert ref.sum() > 0
    np.testing.assert_array_equal(got["gemm"], got["walk"])
    np.testing.assert_array_equal(got["gemm"], ref)


RULE = {
    # id: (max_depth, n_classes, tb2, d, block_obs), path
    "bench-forest": ((8, 7, 64, 54, 128), "gemm"),
    "one-row": ((8, 7, 64, 54, 1), "gemm"),
    "shallow": ((3, 2, 64, 1, 128), "gemm"),
    "widest-bins-and-classes": ((8, 128, 512, 54, 128), "gemm"),
    "depth-9": ((9, 7, 64, 54, 128), "walk"),
    "depth-10": ((10, 7, 64, 55, 128), "walk"),
    "regression": ((8, 0, 64, 54, 128), "walk"),
    "bins-past-256": ((8, 7, 1024, 54, 128), "walk"),
    "129-classes": ((8, 129, 64, 54, 128), "walk"),
    "features-over-budget": ((8, 7, 64, 5000, 128), "walk"),
}


@pytest.mark.parametrize("case", list(RULE), ids=list(RULE))
def test_select_path(case):
    (max_depth, n_classes, tb2, d, bo), path = RULE[case]
    assert tp.select_path(max_depth, n_classes, tb2, d, 8, bo) == path


@pytest.fixture
def launches(monkeypatch):
    """The ``path`` stat of every ``tree_predict.launch`` span opened."""
    seen = []

    def recording(span):
        def wrapped(name, **stats):
            if name == "tree_predict.launch":
                seen.append(stats.get("path"))
            return span(name, **stats)

        return wrapped

    monkeypatch.setattr(tp, "span", recording(tp.span))
    monkeypatch.setattr(ops, "span", recording(ops.span))
    return seen


@pytest.mark.parametrize("n_classes,path", [(3, "gemm"), (0, "walk")],
                         ids=["classification", "regression"])
def test_launch_span_names_the_path(launches, n_classes, path):
    rng = np.random.default_rng(5)
    depth, d, n, bt = 4, 6, 20, 8
    code, fit, _ = _forest(rng, bt, depth, d, max(n_classes, 2))
    xb = rng.integers(0, N_BINS, (n, d)).astype(np.int32)
    oseg = np.zeros(n, np.int32)
    tseg = np.zeros(bt, np.int32)
    lo, hi = tp.segment_chunk_ranges(oseg, tseg, bt, n)
    packed = tp.forest_predict_agg_segmented_packed(
        xb, oseg, jnp.asarray(code), jnp.asarray(fit), tseg, lo, hi,
        depth, TB2, n_classes=n_classes, block_trees=bt,
    )
    sharded = ops.forest_predict_agg_segmented_sharded(
        xb, oseg, code[None], fit[None], tseg[None], lo[None], hi[None],
        depth, TB2, n_classes=n_classes, block_trees=bt,
    )
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(sharded))
    assert launches == [path, path]
