"""Batched random-forest inference Pallas TPU kernels — the paper's serving
hot spot (predict-from-compressed decodes trees, then this evaluates them).

Layout: trees in heap form (node i -> children 2i+1 / 2i+2), so traversal is
pure arithmetic + gathers, no pointers.  Tiling: each program holds a
(BT, Hp) tile of tree arrays and a (BN, d) tile of binned observations in
VMEM and walks ``max_depth`` levels for all (tree, obs) pairs at once — VPU
select ops + MXU one-hot contractions.  Trees are tiny and reused across the
whole observation tile, so the kernel is gather-throughput-bound in VMEM
rather than HBM-bound: per HBM byte of tree data we do BN gathers.

Gathers are TWO-LEVEL: a heap index over ``Hp`` nodes is split into
(hi, lo) = (idx >> 7, idx & 127); a one-hot MXU contraction over ``hi``
picks each pair's 128-lane row of the table, and a lane select-and-sum
picks ``lo`` from it.  The working set is (BT, BN, Hp / 128) + (BT, BN,
128) per element instead of the (BT, BN, Hp) of a flat one-hot.  ``lo``
spans exactly one lane row because Mosaic cannot split the lane dim
below 128.

Three kernels share the traversal:

* ``forest_predict``       -> (T, N) per-(tree, obs) leaf fits;
* ``forest_predict_agg``   -> in-kernel ensemble aggregation over the
  tree-tile grid axis: (N,) fit sums (regression) or (N, C) vote counts
  (classification).  Output HBM traffic shrinks by ~T/block_trees x, and the
  host-side ensemble reduction disappears.
* ``forest_predict_agg_segmented`` -> ragged multi-tenant aggregation: trees
  and observations carry int32 segment (user) ids, and a (tree, obs) pair
  contributes only when the ids match.  Many users' forests pack into ONE
  tree axis (no per-user padding) and one kernel launch serves the whole
  mixed batch — the multi-tenant store's serving front-end
  (``repro.launch.serve_store``).

The segmented kernel comes in TWO engines (``engine=`` on the wrapper):

* ``"simple"``  — the original grid-per-tree-tile kernel, kept as the
  differential oracle and the PR 2 serving baseline;
* ``"pipelined"`` (default when inputs allow) — one launch per batch with a
  MANUAL double-buffered DMA pipeline: tree tiles live in HBM
  (``memory_space=ANY``) and the kernel streams them into two VMEM slots
  with ``pltpu.make_async_copy`` so the NEXT tile's upload overlaps the
  CURRENT tile's traversal.  Two further wins ride on the rework:

  - **fused node attributes**: (feature, threshold, is_internal) pack into
    one power-of-two-scaled float32 code word
    ``feat * 2 * TB + thr * 2 + inter`` (``TB`` = threshold field width
    rounded up to a power of two), so each traversal level performs ONE
    two-level heap gather instead of three.  All field scales are powers
    of two, so the f32 divide/floor decode is exact below 2**24 — the
    wrapper verifies the packed range and falls back to ``"simple"``
    otherwise.
  - **block-diagonal chunk skipping**: per observation block the wrapper
    precomputes (host side) the [lo, hi) range of tree chunks whose
    segment set intersects the block's, shipped via SMEM with the trees'
    segment ids; with rows and
    trees sorted by segment the kernel touches ~sum_u T_u * N_u work, not
    T_total * N_total, in ONE launch with no host round-trips between
    chunks.

  Each streamed chunk is evaluated by one of TWO bodies, chosen from
  static shapes by ``select_path``:

  - ``"walk"``: ``max_depth`` levels of two-level gathers of the fused
    code word plus a lane select of the row's feature, then the leaf fit
    gather and the vote / fit sum (``_walk_votes``).  Regression, trees
    deeper than 8 and wide feature sets take it.
  - ``"gemm"`` (Hummingbird's GEMM strategy, for shallow classification
    forests): per tree three MXU contractions — rows @ feature one-hot
    gives every heap slot's decision bit, bits @ a constant path matrix
    finds the bottom slot each row reaches, and the class one-hot of the
    bottom slots against those hits counts the votes (``_gemm_votes``).
    Every operand is an integer of at most 256, exact in int8 (bins up to
    64) or bfloat16, and every sum is exact in int32 or float32.  The
    bottom slots' classes are derived from the code and fit tables in the
    jitted wrapper (``_gemm_tables``).

Precision guard: node attributes round-trip through float32 gathers (the
one-hot contraction runs at HIGHEST precision), which are exact only
below 2**24 — ``forest_predict*`` validate static shapes and (when inputs
are concrete) data ranges and raise instead of silently corrupting (see
tests/test_serve_path.py boundary test).  The ``gemm`` body rounds
nothing: it never moves a code word through a contraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...runtime.trace import span

_F32_EXACT_INT = 1 << 24  # float32 has a 24-bit significand


def _int32(a):
    """``a`` as int32: a device array stays on the device, anything else
    becomes a host array (for one batched ``jax.device_put``)."""
    if isinstance(a, jax.Array):
        return a if a.dtype == jnp.int32 else a.astype(jnp.int32)
    return np.asarray(a, np.int32)


def _validate_f32_exact(max_depth: int, d: int, **arrays) -> None:
    """Raise if a value routed through the float32 gathers (node fields,
    fused code words, binned features) could exceed the exactly-representable
    integer range.  Node ids stay int32 in the kernels; heaps of 2**24 nodes
    or more are refused as well, their tables being far past any VMEM.

    Host numpy arrays are checked with numpy (free); concrete device arrays
    are checked too, which costs a device sync — hot loops (the streamed
    serve driver) pass numpy tiles so the check never blocks dispatch.
    Tracers can't be value-checked and are skipped."""
    h = (1 << (max_depth + 1)) - 1
    if h >= _F32_EXACT_INT:
        raise ValueError(
            f"max_depth={max_depth} gives {h} heap nodes >= 2**24; the "
            "kernels' heap tables cannot hold that many"
        )
    if d >= _F32_EXACT_INT:
        raise ValueError(f"n_features={d} >= 2**24 overflows float32 gathers")
    for name, arr in arrays.items():
        if isinstance(arr, jax.core.Tracer):
            continue  # under jit/vmap tracing: shapes checked, values can't be
        if not arr.size:
            continue
        if isinstance(arr, np.ndarray):
            big = int(np.max(np.abs(arr))) >= _F32_EXACT_INT
        else:
            big = int(jnp.max(jnp.abs(arr))) >= _F32_EXACT_INT
        if big:
            raise ValueError(
                f"{name} contains values >= 2**24, not exactly representable "
                "in the float32 one-hot gathers"
            )


#: low index bits of the two-level gather: one 128-lane vreg row per ``hi``
#: value, so the heap reshape ``(BT, H) -> (BT, n_hi, 128)`` never splits a
#: lane row (Mosaic refuses shape casts that split the lane dim below 128)
LO_BITS = 7
N_LO = 1 << LO_BITS


def _heap_split(h: int) -> int:
    """``n_hi`` for the two-level gather over ``h`` heap slots; the padded
    heap width is ``n_hi * N_LO``."""
    return max(pl.cdiv(h, N_LO), 1)


def _pad_heap(a: jnp.ndarray, h_pad: int) -> jnp.ndarray:
    t, h = a.shape
    if h == h_pad:
        return a
    return jnp.pad(a, ((0, 0), (0, h_pad - h)))


def _two_level_gather(tab3, idx):
    """``tab3[t, idx[t, n]]`` for tab3 (BT, n_hi, 128) f32 and idx (BT, BN)
    int32 -> (BT, BN) f32.

    The ``hi`` half is a one-hot MXU contraction at HIGHEST precision (the
    default rounds f32 operands to bf16, which would corrupt code words
    above 2**8); the ``lo`` half is a lane select-and-sum.  Each output
    sums exactly one nonzero term, so values below 2**24 come back
    exact."""
    n_hi = tab3.shape[1]
    if n_hi == 1:
        rows = jnp.broadcast_to(
            tab3, (tab3.shape[0], idx.shape[1], N_LO)
        )
    else:
        oh_hi = jax.nn.one_hot(idx >> LO_BITS, n_hi, dtype=jnp.float32)
        rows = jnp.einsum(
            "tnh,thl->tnl", oh_hi, tab3,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )
    lanes = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 2)
    hit = lanes == (idx & (N_LO - 1))[..., None]
    return jnp.where(hit, rows, 0.0).sum(-1)


def _read_feature(fe, xbf):
    """``xbf[n, fe[t, n]]`` for fe (BT, BN) int32 and xbf (BN, d) f32 ->
    (BT, BN) f32: a lane select-and-sum (a batched mat-vec has no
    non-contracting lhs dim, which Mosaic cannot lower)."""
    d = xbf.shape[-1]
    bt, bn = fe.shape
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bt, bn, d), 2)
    hit = lanes == jnp.clip(fe, 0, d - 1)[..., None]
    return jnp.where(hit, xbf[None], 0.0).sum(-1)


def _traverse(xb, node_at, *, max_depth, bt):
    """Shared (BT, BN) heap traversal; returns final node indices.
    ``node_at(idx)`` gathers the (feature int32, threshold f32, internal
    bool) fields of nodes ``idx``."""
    xbf = xb.astype(jnp.float32)

    def level(_, idx):
        fe, th, internal = node_at(idx)
        child = jnp.where(
            _read_feature(fe, xbf) <= th, 2 * idx + 1, 2 * idx + 2
        )
        return jnp.where(internal, child, idx)

    idx = jnp.zeros((bt, xb.shape[0]), jnp.int32)
    return jax.lax.fori_loop(0, max_depth, level, idx)


def _split_fields(feat, thr, inter, n_hi):
    """node_at over three separate (BT, H_pad) attribute tables."""
    bt = feat.shape[0]
    feat3, thr3, inter3 = (
        a.astype(jnp.float32).reshape(bt, n_hi, N_LO)
        for a in (feat, thr, inter)
    )

    def node_at(idx):
        return (
            _two_level_gather(feat3, idx).astype(jnp.int32),
            _two_level_gather(thr3, idx),
            _two_level_gather(inter3, idx) > 0.5,
        )

    return node_at


def _aggregate(leaf, valid, n_classes):
    """(BT, BN) leaf fits + (BT, BN) bool mask -> lane-dense (C, BN) vote
    counts (classification) or (1, BN) fit sums (regression)."""
    if n_classes == 0:
        return jnp.where(valid, leaf, 0.0).sum(0, keepdims=True)
    # one 2-D sublane reduction per class, placed into its output row (a
    # 3-D (C, BT, BN) reduction has no Mosaic lowering)
    cls = jnp.where(valid, leaf.astype(jnp.int32), -1)
    out_row = jax.lax.broadcasted_iota(
        jnp.int32, (n_classes, leaf.shape[1]), 0
    )
    votes = jnp.zeros(out_row.shape, jnp.float32)
    for c in range(n_classes):
        count = (cls == c).astype(jnp.float32).sum(0, keepdims=True)
        votes = jnp.where(out_row == c, count, votes)
    return votes


def _tree_predict_kernel(
    xb_ref, feat_ref, thr_ref, fit_ref, inter_ref, out_ref,
    *, max_depth: int, n_hi: int,
):
    bt = fit_ref.shape[0]
    idx = _traverse(
        xb_ref[...],
        _split_fields(feat_ref[...], thr_ref[...], inter_ref[...], n_hi),
        max_depth=max_depth, bt=bt,
    )
    fit3 = fit_ref[...].reshape(bt, n_hi, N_LO)
    out_ref[...] = _two_level_gather(fit3, idx)


def _tree_predict_agg_kernel(
    xb_ref, feat_ref, thr_ref, fit_ref, inter_ref, out_ref,
    *, max_depth: int, n_hi: int, n_classes: int, block_trees: int,
    n_trees: int,
):
    bt = fit_ref.shape[0]
    idx = _traverse(
        xb_ref[...],
        _split_fields(feat_ref[...], thr_ref[...], inter_ref[...], n_hi),
        max_depth=max_depth, bt=bt,
    )
    leaf = _two_level_gather(fit_ref[...].reshape(bt, n_hi, N_LO), idx)
    # mask trees past T (grid padding): their tile rows hold garbage
    j = pl.program_id(1)
    tree_ids = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 0)
    valid = tree_ids + j * block_trees < n_trees

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _aggregate(leaf, valid, n_classes)


@functools.partial(
    jax.jit,
    static_argnames=("max_depth", "block_trees", "block_obs", "interpret"),
)
def _forest_predict_impl(
    xb, feature, threshold, fit, is_internal,
    max_depth, block_trees, block_obs, interpret,
):
    t, h = feature.shape
    n, d = xb.shape
    n_hi = _heap_split(h)
    h_pad = n_hi * N_LO
    feature, threshold, fit, inter = (
        _pad_heap(a, h_pad)
        for a in (feature, threshold, fit, is_internal.astype(jnp.int32))
    )
    grid = (pl.cdiv(t, block_trees), pl.cdiv(n, block_obs))
    kernel = functools.partial(
        _tree_predict_kernel, max_depth=max_depth, n_hi=n_hi,
    )
    tree_spec = lambda: pl.BlockSpec((block_trees, h_pad), lambda i, j: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_obs, d), lambda i, j: (j, 0)),
            tree_spec(), tree_spec(), tree_spec(), tree_spec(),
        ],
        out_specs=pl.BlockSpec(
            (block_trees, block_obs), lambda i, j: (i, j)
        ),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        interpret=interpret,
        name="tree_predict",
    )(xb, feature, threshold, fit, inter)


def forest_predict(
    xb: jnp.ndarray,  # (N, d) int32
    feature: jnp.ndarray,  # (T, H) int32
    threshold: jnp.ndarray,  # (T, H) int32
    fit: jnp.ndarray,  # (T, H) float32
    is_internal: jnp.ndarray,  # (T, H) bool
    max_depth: int,
    block_trees: int = 8,
    block_obs: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (T, N) per-(tree, obs) leaf fits."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t, _ = feature.shape
    n, d = xb.shape
    _validate_f32_exact(
        max_depth, d, feature=feature, threshold=threshold, xb=xb
    )
    return _forest_predict_impl(
        xb, feature, threshold, fit, is_internal,
        max_depth, min(block_trees, t), min(block_obs, n), interpret,
    )


def _tree_predict_agg_seg_kernel(
    xb_ref, oseg_ref, tseg_ref, feat_ref, thr_ref, fit_ref, inter_ref,
    out_ref,
    *, max_depth: int, n_hi: int, n_classes: int, block_trees: int,
    n_trees: int,
):
    bt = fit_ref.shape[0]
    idx = _traverse(
        xb_ref[...],
        _split_fields(feat_ref[...], thr_ref[...], inter_ref[...], n_hi),
        max_depth=max_depth, bt=bt,
    )
    leaf = _two_level_gather(fit_ref[...].reshape(bt, n_hi, N_LO), idx)
    # a (tree, obs) pair contributes iff the tree is real (grid padding) AND
    # its segment (user) id matches the observation's segment id
    j = pl.program_id(1)
    tree_ids = jax.lax.broadcasted_iota(jnp.int32, idx.shape, 0)
    in_range = tree_ids + j * block_trees < n_trees
    same_seg = tseg_ref[...] == oseg_ref[...]  # (BT,1) vs (1,BN) -> (BT,BN)

    @pl.when(j == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += _aggregate(leaf, in_range & same_seg, n_classes)


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_classes", "block_trees", "block_obs", "interpret"
    ),
)
def _forest_predict_agg_seg_impl(
    xb, obs_seg, tree_seg, feature, threshold, fit, is_internal,
    max_depth, n_classes, block_trees, block_obs, interpret,
):
    t, h = feature.shape
    n, d = xb.shape
    n_hi = _heap_split(h)
    h_pad = n_hi * N_LO
    feature, threshold, fit, inter = (
        _pad_heap(a, h_pad)
        for a in (feature, threshold, fit, is_internal.astype(jnp.int32))
    )
    c_out = n_classes if n_classes > 0 else 1
    # tree tiles on the LAST grid axis (same reason as the unsegmented agg
    # kernel: consecutive steps revisit the same output block for +=)
    grid = (pl.cdiv(n, block_obs), pl.cdiv(t, block_trees))
    kernel = functools.partial(
        _tree_predict_agg_seg_kernel,
        max_depth=max_depth, n_hi=n_hi, n_classes=n_classes,
        block_trees=block_trees, n_trees=t,
    )
    tree_spec = lambda: pl.BlockSpec((block_trees, h_pad), lambda i, j: (j, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_obs, d), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_obs), lambda i, j: (0, i)),
            pl.BlockSpec((block_trees, 1), lambda i, j: (j, 0)),
            tree_spec(), tree_spec(), tree_spec(), tree_spec(),
        ],
        out_specs=pl.BlockSpec((c_out, block_obs), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c_out, n), jnp.float32),
        interpret=interpret,
        name="tree_predict_agg_seg",
    )(xb, obs_seg, tree_seg, feature, threshold, fit, inter)
    return out[0] if n_classes == 0 else out.T


def _forest_predict_agg_segmented_simple(
    xb, obs_seg, tree_seg, feature, threshold, fit, is_internal,
    max_depth, n_classes, block_trees, block_obs, interpret,
):
    """The original segmented kernel (PR 2) — grid over (obs, tree) tiles
    with += accumulation.  Kept verbatim as the ``engine="simple"`` oracle
    and serving baseline."""
    t, _ = feature.shape
    n, d = xb.shape
    with span("serve.prep"):
        _validate_f32_exact(
            max_depth, d, feature=feature, threshold=threshold, xb=xb
        )
    if n_classes > 0 and n_classes >= _F32_EXACT_INT:
        raise ValueError("n_classes >= 2**24 overflows float32 vote counts")
    with span("tree_predict.upload"):
        args = jax.device_put([
            _int32(xb), _int32(obs_seg).reshape(1, n),
            _int32(tree_seg).reshape(t, 1), feature, threshold, fit,
            is_internal,
        ])
    with span("tree_predict.launch", path="walk"):
        return _forest_predict_agg_seg_impl(
            *args, max_depth, n_classes, min(block_trees, t),
            min(block_obs, n), interpret,
        )


# ---------------------------------------------------------------------------
# Pipelined engine: fused node attributes + double-buffered DMA over chunks
# ---------------------------------------------------------------------------

def fused_threshold_base(max_threshold: int) -> int:
    """``TB``: threshold field width of the fused code word, rounded up to a
    power of two so every decode divide/floor is exact in float32."""
    return 1 << max(int(max_threshold), 1).bit_length()


def fuse_node_attrs(
    feature: np.ndarray, threshold: np.ndarray, is_internal: np.ndarray,
    tb: int,
) -> np.ndarray:
    """Pack (feature, threshold, is_internal) into one float32 code table:
    ``code = (feature * TB + threshold) * 2 + is_internal``.  Requires
    non-negative fields, ``threshold < TB``, and the packed range below
    2**24 (caller-checked via ``fused_code_limit``)."""
    code = (
        np.asarray(feature, np.int64) * (2 * tb)
        + np.asarray(threshold, np.int64) * 2
        + np.asarray(is_internal, np.int64)
    )
    return code.astype(np.float32)


def fused_code_limit(d: int, tb: int) -> int:
    """Largest code word the fused packing can produce: feature d-1,
    threshold TB-1, internal 1."""
    return (d - 1) * 2 * tb + (tb - 1) * 2 + 1


def segment_chunk_ranges(
    obs_seg: np.ndarray,  # (N,) int32, any order (sorted => tight ranges)
    tree_seg: np.ndarray,  # (T_pad,) int32, -1 = padding
    block_trees: int,
    block_obs: int,
) -> tuple[np.ndarray, np.ndarray]:
    """Per observation block, the [lo, hi) range of tree chunks whose
    segment set intersects the block's — the kernel's fori_loop bounds.

    Always CORRECT for any ordering (the in-kernel segment mask filters
    non-matching pairs); TIGHT when rows and trees are sorted by segment,
    where it recovers the block-diagonal work bound ~sum_u T_u * N_u."""
    obs_seg = np.asarray(obs_seg, np.int64)
    tree_seg = np.asarray(tree_seg, np.int64)
    n, t_pad = len(obs_seg), len(tree_seg)
    n_chunks = t_pad // block_trees
    g = max(-(-n // block_obs), 1)
    n_segs = int(max(obs_seg.max(initial=0), tree_seg.max(initial=0))) + 1
    # membership matrices via one flat scatter each; segment -1 (padding)
    # lands in the dropped 0th column
    chunk_of = np.repeat(np.arange(n_chunks), block_trees)
    seg_in_chunk = np.zeros((n_chunks, n_segs + 1), bool)
    seg_in_chunk[chunk_of, np.clip(tree_seg, -1, n_segs - 1) + 1] = True
    block_of = np.repeat(np.arange(g), block_obs)[:n]
    seg_in_block = np.zeros((g, n_segs + 1), bool)
    seg_in_block[block_of, np.clip(obs_seg, -1, n_segs - 1) + 1] = True
    need = seg_in_block[:, 1:] @ seg_in_chunk[:, 1:].T  # (g, n_chunks)
    any_ = need.any(1)
    lo = np.where(any_, need.argmax(1), 0).astype(np.int32)
    hi = np.where(
        any_, n_chunks - need[:, ::-1].argmax(1), 0
    ).astype(np.int32)
    return lo, hi


#: the ``gemm`` body's limits: a path matrix at most 256 wide, at most
#: 128 classes, and bins that clamp to ``TB`` exactly in bfloat16
GEMM_MAX_DEPTH = 8
GEMM_MAX_CLASSES = 128
GEMM_MAX_TB = 256
#: VMEM the ``gemm`` body's blocks and a chunk's temporaries may take
#: (half of v5e's default scoped limit); wider feature sets walk
GEMM_VMEM_BUDGET = 8 << 20


def _round_up(a: int, m: int) -> int:
    return -(-a // m) * m


def _gemm_features(d: int) -> int:
    """Feature axis of the ``gemm`` body's row block: int8's sublane
    tile."""
    return _round_up(d, 32)


def _gemm_operand(tb2: int):
    """The ``gemm`` body's operand type: int8 while rows clamped to ``TB``
    fit it (the MXU's int8 rate is twice its bfloat16 rate), else
    bfloat16; both hold every operand exactly."""
    return jnp.int8 if tb2 // 2 <= 64 else jnp.bfloat16


def _gemm_accumulator(dt):
    return jnp.int32 if dt == jnp.int8 else jnp.float32


def _gemm_width(max_depth: int) -> int:
    """Lanes of the ``gemm`` body's slot axis: ``2**max_depth`` bottom
    slots (and the internal slots above them), at least one lane row."""
    return max(1 << max_depth, N_LO)


def _gemm_vmem_bytes(block_trees, block_obs, d_pad, width) -> int:
    """Bytes the ``gemm`` body holds at once, at bfloat16 operands: every
    tree's temporaries of a chunk are live together."""
    rows = 2 * block_obs * d_pad * 2  # row block, double-buffered
    tables = 2 * 2 * block_trees * width * 4  # code and class slots
    consts = width * width * 2 + width * 4  # path matrix and left turns
    one_hot = block_trees * d_pad * width * (4 + 2)  # compare, operand
    step = block_trees * block_obs * width * (4 + 2) * 2  # select, turns
    return rows + tables + consts + one_hot + step


def select_path(
    max_depth: int, n_classes: int, tb2: int, d: int, block_trees: int,
    block_obs: int,
) -> str:
    """The pipelined kernel's traversal body for these static shapes:
    ``"gemm"`` (three MXU contractions per tree chunk) for shallow
    classification forests, else ``"walk"`` (``max_depth`` levels of heap
    gathers).  Regression walks: MXU summation would reorder its float32
    sums."""
    if not (
        1 <= n_classes <= GEMM_MAX_CLASSES
        and max_depth <= GEMM_MAX_DEPTH
        and tb2 // 2 <= GEMM_MAX_TB
    ):
        return "walk"
    need = _gemm_vmem_bytes(
        block_trees, block_obs, _gemm_features(d), _gemm_width(max_depth)
    )
    return "gemm" if need <= GEMM_VMEM_BUDGET else "walk"


@functools.lru_cache(maxsize=None)
def _path_tables(max_depth: int) -> tuple[np.ndarray, np.ndarray]:
    """The ``gemm`` body's constants over ``W = _gemm_width(max_depth)``
    slots: ``C`` (W, W) with ``C[i, s]`` = +1 if bottom slot ``s`` lies in
    the left subtree of heap slot ``i``, -1 if in the right, else 0; and
    ``D`` (1, W), the left turns on the way to ``s`` (-1 past the bottom
    level, which no row reaches).  A row's decision bits ``b`` reach
    exactly the ``s`` with ``(b @ C)[s] == D[s]``."""
    w = _gemm_width(max_depth)
    n_bot = 1 << max_depth
    c = np.zeros((w, w), np.float32)
    lefts = np.full((1, w), -1.0, np.float32)
    for s in range(n_bot):
        node, turns = n_bot - 1 + s, 0
        while node:
            parent = (node - 1) // 2
            left = node == 2 * parent + 1
            c[parent, s] = 1.0 if left else -1.0
            turns += left
            node = parent
        lefts[0, s] = turns
    return c, lefts


@functools.lru_cache(maxsize=None)
def _bottom_ancestors(max_depth: int) -> np.ndarray:
    """(max_depth + 1, 2**max_depth) heap slot of each bottom slot's
    ancestor at each level, root first, the bottom slot itself last."""
    node = np.arange(1 << max_depth) + (1 << max_depth)  # 1-based heap
    return np.stack(
        [(node >> (max_depth - k)) - 1 for k in range(max_depth + 1)]
    ).astype(np.int32)


def _gemm_tables(code, fit, max_depth, width):
    """(T, W) slot tables for the ``gemm`` body, in XLA on the device:
    the fused code words of heap slots 0..W-1, and per bottom slot the
    class its rows vote, the fit of its first non-internal ancestor or
    itself (the node a ``max_depth``-level walk stops at).  One gather of
    each table along the bottom slots' paths, so the call adds a few
    device ops, not a few per level."""
    n_bot = 1 << max_depth
    code = _pad_heap(code, max(code.shape[1], 2 * n_bot - 1, width))
    fit = _pad_heap(fit, max(fit.shape[1], 2 * n_bot - 1))
    anc = _bottom_ancestors(max_depth)
    level = np.arange(max_depth + 1).reshape(-1, 1)
    # a walk stops at its first leaf, or at the bottom after max_depth
    stop = ((code[:, anc].astype(jnp.int32) & 1) == 0) | (level == max_depth)
    first = jnp.where(stop, level, max_depth).min(axis=1, keepdims=True)
    # the walk's class is its leaf fit truncated to int32
    cls = jnp.where(level == first, fit[:, anc], 0.0).sum(1).astype(jnp.int32)
    # no row reaches a lane past the bottom level (left-turn count -1)
    return code[:, :width], _pad_heap(cls, width).astype(jnp.float32)


def _walk_votes(xb, osegs, tseg_ref, *, max_depth, n_hi, n_classes,
                block_trees, tb2):
    """The ``walk`` body: per chunk, ``max_depth`` levels of two-level
    heap gathers of the fused code word, a leaf gather of the fit, and the
    segment-masked vote count."""
    bn = xb.shape[0]
    tree_row = jax.lax.broadcasted_iota(jnp.int32, (block_trees, bn), 0)

    def fields(code3):
        def node_at(idx):
            c = _two_level_gather(code3, idx)
            # power-of-two field scales: the reciprocal is exact, and so
            # is the multiply/floor decode
            fe = jnp.floor(c * (1.0 / tb2))
            rem = c - fe * tb2
            th = jnp.floor(rem * 0.5)
            return fe.astype(jnp.int32), th, rem - 2.0 * th > 0.5

        return node_at

    def chunk_votes(ci, tables):
        # the chunk's segment ids: scalar SMEM reads broadcast into rows
        # (a (BT, 1) int32 DMA slice is not lane-aligned), before the
        # wait for the chunk's DMAs
        tseg = jnp.full((block_trees, bn), -1, jnp.int32)
        for t in range(block_trees):
            tseg = jnp.where(
                tree_row == t, tseg_ref[ci * block_trees + t], tseg
            )
        code, fit = tables()
        code3 = code.reshape(block_trees, n_hi, N_LO)
        idx = _traverse(
            xb, fields(code3), max_depth=max_depth, bt=block_trees
        )
        leaf = _two_level_gather(
            fit.reshape(block_trees, n_hi, N_LO), idx
        )  # (BT, BN)
        # padding trees carry segment -1, which never matches a row
        return _aggregate(leaf, tseg == osegs, n_classes)

    return chunk_votes


def _gemm_votes(xb, osegs, tseg_ref, turn_ref, lefts_ref, *, n_classes,
                block_trees, tb2, n_features):
    """The ``gemm`` body: per tree of a chunk, three contractions of
    small-integer operands, exact in the row block's type (int8 or
    bfloat16, ``_gemm_operand``) with int32 or float32 sums.

    1. feature select: rows (BN, d_pad) @ one-hot of each slot's feature
       (d_pad, W) gives every slot's feature value for every row, and
       ``x <= thr`` the decision bits of every slot, leaf or padding too
       (a row below a leaf still lands under that leaf);
    2. path match: bits @ ``C`` equals ``D`` at exactly the bottom slot
       the row reaches (``_path_tables``);
    3. vote: the one-hot of each bottom slot's class (C_pad, W) against
       the hits (BN, W), contracted over W, gives (C_pad, BN) votes."""
    d_pad = xb.shape[1]
    dt = xb.dtype
    acc = _gemm_accumulator(dt)
    c_pad = _round_up(n_classes, 32 if dt == jnp.int8 else 16)  # sublanes
    turn = turn_ref[...]
    lefts = lefts_ref[...].astype(acc)

    def chunk_votes(ci, tables):
        code, cls = tables()
        width = code.shape[1]
        # the walk's decode; feature ids clipped into [0, d) as it reads
        fe = jnp.floor(code * (1.0 / tb2))
        th = jnp.floor((code - fe * tb2) * 0.5).astype(acc)
        fe = jnp.clip(fe, 0.0, float(n_features - 1)).astype(jnp.int32)
        cls = cls.astype(jnp.int32)
        feat_of = jax.lax.broadcasted_iota(jnp.int32, (d_pad, width), 0)
        class_of = jax.lax.broadcasted_iota(jnp.int32, (c_pad, width), 0)
        bn = xb.shape[0]
        trees = range(block_trees)
        # each contraction for every tree before the next one's: the
        # trees' contractions then overlap on the MXU, where three
        # dependent ones per tree wait on each other (2.8x slower, v5e)
        bits = [
            (jnp.dot(xb, (feat_of == fe[t:t + 1]).astype(dt),
                     preferred_element_type=acc) <= th[t:t + 1]).astype(dt)
            for t in trees
        ]
        hits = [
            (jnp.dot(b, turn, preferred_element_type=acc) == lefts).astype(dt)
            for b in bits
        ]
        votes = jnp.zeros((c_pad, bn), acc)
        for t in trees:
            voted = class_of == cls[t:t + 1]
            if bn == 1:  # Mosaic cannot lower a one-column dot
                v = jnp.where(voted, hits[t].astype(acc), 0).sum(
                    1, keepdims=True
                )
            else:
                v = jax.lax.dot_general(
                    voted.astype(dt), hits[t], (((1,), (1,)), ((), ())),
                    preferred_element_type=acc,
                )
            # padding trees carry segment -1, which never matches a row
            same = osegs == tseg_ref[ci * block_trees + t]
            votes = votes + jnp.where(same, v, 0)
        return votes[:n_classes].astype(jnp.float32)

    return chunk_votes


def _tree_predict_agg_seg_pipelined_kernel(
    chunk_lo_ref, chunk_hi_ref,  # SMEM (G,) int32 fori_loop bounds
    tseg_ref,  # SMEM (T_pad,) int32 segment id per tree
    xb_ref, oseg_ref,  # VMEM blocks
    *refs,  # gemm: path matrix, left turns (VMEM); then the two
    # per-tree tables in ANY/HBM, DMA'd per chunk; then the output
    path: str, max_depth: int, n_hi: int, n_classes: int, block_trees: int,
    tb2: float, n_features: int,
):
    *consts, code_hbm, tab_hbm, out_ref = refs
    i = pl.program_id(0)
    lo = chunk_lo_ref[i]
    hi = chunk_hi_ref[i]
    bn = xb_ref.shape[0]
    width = code_hbm.shape[1]
    xb = xb_ref[...]
    osegs = oseg_ref[...]  # (1, BN)
    if path == "gemm":
        chunk_votes = _gemm_votes(
            xb, osegs, tseg_ref, *consts, n_classes=n_classes,
            block_trees=block_trees, tb2=tb2, n_features=n_features,
        )
    else:
        chunk_votes = _walk_votes(
            xb, osegs, tseg_ref, max_depth=max_depth, n_hi=n_hi,
            n_classes=n_classes, block_trees=block_trees, tb2=tb2,
        )

    def body(code_s, tab_s, sems):
        # one DMA pair per (slot, chunk); fresh descriptors are cheap —
        # start() and wait() pair up through the per-(slot, k) semaphore
        def dma(slot, ci, k):
            src, dst = ((code_hbm, code_s), (tab_hbm, tab_s))[k]
            return pltpu.make_async_copy(
                src.at[pl.ds(ci * block_trees, block_trees)],
                dst.at[slot],
                sems.at[slot, k],
            )

        @pl.when(lo < hi)
        def _():  # warm-up: fill slot 0 before the steady-state loop
            for k in range(2):
                dma(0, lo, k).start()

        def chunk_step(step, acc):
            ci = lo + step
            cur = step % 2

            @pl.when(ci + 1 < hi)
            def _():  # overlap: next chunk uploads while this one computes
                for k in range(2):
                    dma((step + 1) % 2, ci + 1, k).start()

            def tables():  # the chunk's two tables, once they landed
                for k in range(2):
                    dma(cur, ci, k).wait()
                return code_s[cur], tab_s[cur]

            return acc + chunk_votes(ci, tables)

        out_ref[...] = jax.lax.fori_loop(
            0, hi - lo, chunk_step,
            jnp.zeros((out_ref.shape[0], bn), jnp.float32),
        )

    pl.run_scoped(
        body,
        pltpu.VMEM((2, block_trees, width), jnp.float32),
        pltpu.VMEM((2, block_trees, width), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 2)),
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_classes", "block_trees", "block_obs", "tb2",
        "interpret", "path",
    ),
)
def _forest_predict_agg_seg_pipelined_impl(
    xb, obs_seg, code, fit, tree_seg, chunk_lo, chunk_hi,
    max_depth, n_classes, block_trees, block_obs, tb2, interpret, path,
):
    """``path`` is the traversal body, ``select_path``'s answer for these
    shapes (tests force either)."""
    t_pad, h = code.shape
    n, d = xb.shape
    n_hi = _heap_split(h)
    c_out = n_classes if n_classes > 0 else 1
    consts, const_specs = [], []
    if path == "gemm":
        # rows clamp to TB: every x <= thr (thr < TB) keeps its answer,
        # and every value is an integer of at most TB, exact in ``dt``
        dt = _gemm_operand(tb2)
        d_pad = _gemm_features(d)
        xb = jnp.pad(
            jnp.clip(xb, -1, tb2 // 2).astype(dt), ((0, 0), (0, d_pad - d))
        )
        code, tab = _gemm_tables(code, fit, max_depth, _gemm_width(max_depth))
        turn, lefts = _path_tables(max_depth)
        consts = [jnp.asarray(turn, dt), jnp.asarray(lefts)]
        const_specs = [
            pl.BlockSpec(a.shape, lambda i: (0, 0)) for a in consts
        ]
    else:
        h_pad = n_hi * N_LO
        code = _pad_heap(code, h_pad)
        tab = _pad_heap(fit, h_pad)
    kernel = functools.partial(
        _tree_predict_agg_seg_pipelined_kernel,
        path=path, max_depth=max_depth, n_hi=n_hi, n_classes=n_classes,
        block_trees=block_trees, tb2=float(tb2), n_features=d,
    )
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, block_obs),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_obs, xb.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((1, block_obs), lambda i: (0, i)),
            *const_specs,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((c_out, block_obs), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c_out, n), jnp.float32),
        interpret=interpret,
        name="tree_predict_agg_seg_pipelined",
    )(
        chunk_lo, chunk_hi, tree_seg, xb, obs_seg.reshape(1, n), *consts,
        code, tab,
    )
    return out[0] if n_classes == 0 else out.T


def forest_predict_agg_segmented_packed(
    xb,  # (N, d) int32
    obs_seg,  # (N,) int32
    code,  # (T_pad, H) float32 fused node attrs (fuse_node_attrs)
    fit,  # (T_pad, H) float32
    tree_seg,  # (T_pad,) int32, -1 marks padding trees
    chunk_lo,  # (ceil(N / block_obs),) int32
    chunk_hi,  # (ceil(N / block_obs),) int32
    max_depth: int,
    tb2: int,  # 2 * fused_threshold_base(...)
    n_classes: int = 0,
    block_trees: int = 8,
    block_obs: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Low-level pipelined entry for PRE-FUSED tree tiles (the device tile
    arena stores this layout): one launch, double-buffered DMA over tree
    chunks.  ``T_pad`` must be a positive multiple of ``block_trees`` with
    padding trees marked ``tree_seg == -1``."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t_pad, _ = code.shape
    n, d = xb.shape
    if t_pad % block_trees != 0 or t_pad == 0:
        raise ValueError(
            f"T_pad={t_pad} must be a positive multiple of "
            f"block_trees={block_trees}"
        )
    if n_classes > 0 and n_classes >= _F32_EXACT_INT:
        raise ValueError("n_classes >= 2**24 overflows float32 vote counts")
    # value-check code only when it is a host array: device-resident code
    # comes from the arena, whose constructor already rejects schemas that
    # could reach 2**24 — re-reducing it here would force a device sync on
    # every serving batch and serialize the dispatch the pipeline overlaps
    arrays = {"xb": xb}
    if isinstance(code, np.ndarray):
        arrays["code"] = code
    with span("serve.prep"):
        _validate_f32_exact(max_depth, d, **arrays)
    # rows, segment ids and ranges go up in one batched transfer; code and
    # fit are device arrays in serving (the arena's gathers)
    with span("tree_predict.upload"):
        xb, obs_seg, tree_seg, chunk_lo, chunk_hi = jax.device_put([
            _int32(a) for a in (xb, obs_seg, tree_seg, chunk_lo, chunk_hi)
        ])
    block_obs = min(block_obs, n)
    path = select_path(max_depth, n_classes, tb2, d, block_trees, block_obs)
    with span("tree_predict.launch", path=path):
        return _forest_predict_agg_seg_pipelined_impl(
            xb, obs_seg, code, fit, tree_seg, chunk_lo, chunk_hi,
            max_depth, n_classes, block_trees, block_obs, int(tb2),
            interpret, path,
        )


def _is_concrete(*arrays) -> bool:
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)


def forest_predict_agg_segmented(
    xb: jnp.ndarray,  # (N, d) int32
    obs_seg: jnp.ndarray,  # (N,) or (N, 1) int32 segment (user) id per row
    tree_seg: jnp.ndarray,  # (T,) or (T, 1) int32 segment (user) id per tree
    feature: jnp.ndarray,  # (T, H) int32
    threshold: jnp.ndarray,  # (T, H) int32
    fit: jnp.ndarray,  # (T, H) float32 (class ids for classification)
    is_internal: jnp.ndarray,  # (T, H) bool
    max_depth: int,
    n_classes: int = 0,
    block_trees: int = 8,
    block_obs: int = 256,
    interpret: bool | None = None,
    engine: str | None = None,
) -> jnp.ndarray:
    """Ragged multi-tenant serving kernel: per-row ensemble aggregation
    restricted to the trees whose segment id matches the row's.

    Trees from MANY users' forests concatenate along the T axis (ragged —
    users need not have equal tree counts) and a mixed batch of many users'
    observations concatenates along N; one launch returns, per row, the
    (N,) fit sum / (N, C) vote counts over that row's own forest only.
    Segment ids are compared as int32 inside the kernel (they never route
    through the float32 one-hot gathers), so any int32 id is safe.

    ``engine``: ``"pipelined"`` (fused-attribute double-buffered DMA, one
    launch), ``"simple"`` (the PR 2 oracle), or ``None`` to pick
    ``"pipelined"`` whenever the inputs are concrete, the node attributes
    are non-negative, and the fused code word fits below 2**24.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t, _ = feature.shape
    n, d = xb.shape
    obs_seg = (
        obs_seg.reshape(-1) if hasattr(obs_seg, "reshape") else obs_seg
    )
    tree_seg = (
        tree_seg.reshape(-1) if hasattr(tree_seg, "reshape") else tree_seg
    )
    if engine is None or engine == "pipelined":
        eligible = t > 0 and n > 0 and _is_concrete(
            xb, obs_seg, tree_seg, feature, threshold, fit, is_internal
        )
        if eligible:
            feat_h = np.asarray(feature)
            thr_h = np.asarray(threshold)
            tb = fused_threshold_base(int(thr_h.max(initial=0)))
            eligible = (
                int(feat_h.min(initial=0)) >= 0
                and int(thr_h.min(initial=0)) >= 0
                and fused_code_limit(d, tb) < _F32_EXACT_INT
            )
        if not eligible:
            if engine == "pipelined":
                raise ValueError(
                    "engine='pipelined' needs concrete non-negative "
                    "feature/threshold arrays whose fused code word fits "
                    "below 2**24 (and a non-empty batch)"
                )
            engine = "simple"
        else:
            code = fuse_node_attrs(
                feat_h, thr_h, np.asarray(is_internal), tb
            )
            block_trees = min(block_trees, t)
            t_pad = -(-t // block_trees) * block_trees
            tseg_h = np.asarray(tree_seg, np.int32)
            pad = t_pad - t
            if pad:
                code = np.pad(code, ((0, pad), (0, 0)))
                fit = np.pad(np.asarray(fit), ((0, pad), (0, 0)))
                tseg_h = np.pad(tseg_h, (0, pad), constant_values=-1)
            oseg_h = np.asarray(obs_seg, np.int32)
            block_obs = min(block_obs, n)
            chunk_lo, chunk_hi = segment_chunk_ranges(
                oseg_h, tseg_h, block_trees, block_obs
            )
            with span("tree_predict.upload"):
                code = jnp.asarray(code)
                fit = jnp.asarray(fit, jnp.float32)
            return forest_predict_agg_segmented_packed(
                xb, oseg_h, code, fit, tseg_h, chunk_lo, chunk_hi,
                max_depth, 2 * tb,
                n_classes=n_classes, block_trees=block_trees,
                block_obs=block_obs, interpret=interpret,
            )
    if engine != "simple":
        raise ValueError(f"unknown segmented engine {engine!r}")
    return _forest_predict_agg_segmented_simple(
        xb, obs_seg, tree_seg, feature, threshold, fit, is_internal,
        max_depth, n_classes, block_trees, block_obs, interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_classes", "block_trees", "block_obs", "interpret"
    ),
)
def _forest_predict_agg_impl(
    xb, feature, threshold, fit, is_internal,
    max_depth, n_classes, block_trees, block_obs, interpret,
):
    t, h = feature.shape
    n, d = xb.shape
    n_hi = _heap_split(h)
    h_pad = n_hi * N_LO
    feature, threshold, fit, inter = (
        _pad_heap(a, h_pad)
        for a in (feature, threshold, fit, is_internal.astype(jnp.int32))
    )
    c_out = n_classes if n_classes > 0 else 1
    # tree tiles on the LAST grid axis: consecutive steps revisit the same
    # output block, which is what makes the += accumulation well-defined
    grid = (pl.cdiv(n, block_obs), pl.cdiv(t, block_trees))
    kernel = functools.partial(
        _tree_predict_agg_kernel,
        max_depth=max_depth, n_hi=n_hi, n_classes=n_classes,
        block_trees=block_trees, n_trees=t,
    )
    tree_spec = lambda: pl.BlockSpec((block_trees, h_pad), lambda i, j: (j, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_obs, d), lambda i, j: (i, 0)),
            tree_spec(), tree_spec(), tree_spec(), tree_spec(),
        ],
        out_specs=pl.BlockSpec((c_out, block_obs), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((c_out, n), jnp.float32),
        interpret=interpret,
        name="tree_predict_agg",
    )(xb, feature, threshold, fit, inter)
    return out[0] if n_classes == 0 else out.T


def forest_predict_agg(
    xb: jnp.ndarray,  # (N, d) int32
    feature: jnp.ndarray,  # (T, H) int32
    threshold: jnp.ndarray,  # (T, H) int32
    fit: jnp.ndarray,  # (T, H) float32 (class ids for classification)
    is_internal: jnp.ndarray,  # (T, H) bool
    max_depth: int,
    n_classes: int = 0,
    block_trees: int = 8,
    block_obs: int = 256,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Fused decode->predict serving kernel with IN-KERNEL ensemble
    aggregation across the tree-tile grid axis.

    Returns (N,) summed leaf fits when ``n_classes == 0`` (regression; divide
    by T for the ensemble mean) or (N, C) per-class vote counts otherwise —
    HBM output traffic is O(N) instead of O(T * N).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    t, _ = feature.shape
    n, d = xb.shape
    _validate_f32_exact(
        max_depth, d, feature=feature, threshold=threshold, xb=xb
    )
    if n_classes > 0 and n_classes >= _F32_EXACT_INT:
        raise ValueError("n_classes >= 2**24 overflows float32 vote counts")
    return _forest_predict_agg_impl(
        xb, feature, threshold, fit, is_internal,
        max_depth, n_classes, min(block_trees, t), min(block_obs, n),
        interpret,
    )
