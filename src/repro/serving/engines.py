"""Engine execution for the serving session (ISSUE 4).

The three engines PR 2/3 grew inside ``launch.serve_store`` now execute a
``ServePlan`` against a row block: each takes ``(store, plan, xb)`` and
returns the raw per-row aggregate (``(N, C)`` vote counts or ``(N,)`` fit
sums) in ORIGINAL request order — the server's finalize step turns that
into per-request predictions.

The pipelined and sharded engines split into ``build_*_pack`` (arena
ensure + device index-gather + chunk ranges — the part ``PlanCache``
memoizes across batches) and ``run_*`` (the kernel launch, paid per
batch).  ``run_simple`` is the PR 2 host-pack path kept verbatim as the
differential oracle and benchmark baseline.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from ..runtime.trace import span
from .pack import pack_host_tiles
from .plan import ServePlan


def _n_classes(store) -> int:
    shared = store.shared
    return shared.n_classes if shared.task == "classification" else 0


def _unpermute(plan: ServePlan, out: np.ndarray) -> np.ndarray:
    """The aggregate of the segment-sorted rows back in request order."""
    with span("serve.finalize"):
        total = np.empty_like(out)
        total[plan.order] = out
    return total


class PipelinedPack(NamedTuple):
    """Arena-gathered device arrays + chunk ranges for one plan — the
    cross-batch memoizable artifact of the pipelined engine."""

    code: object  # (T_pad, H) f32 device
    fit: object  # (T_pad, H) f32 device
    tree_seg: np.ndarray  # (T_pad,) int32, -1 padding
    counts: np.ndarray  # (S,) int64
    max_depth: int
    chunk_lo: np.ndarray  # (ceil(N / block_obs),) int32
    chunk_hi: np.ndarray
    block_obs: int  # block_obs AFTER the min(N) clamp


class ShardedPack(NamedTuple):
    """Per-device stacked gathers + ranges for the sharded engine."""

    code: object  # (S_dev, T_pad, H) f32 device
    fit: object
    tree_seg: np.ndarray  # (S_dev, T_pad) int32
    chunk_lo: np.ndarray  # (S_dev, G) int32
    chunk_hi: np.ndarray
    max_depth: int
    block_obs: int


# ---------------------------------------------------------------------------
# simple — the PR 2 oracle: host tile pack + one launch per tree chunk
# ---------------------------------------------------------------------------

def run_simple(
    store, plan: ServePlan, xb: np.ndarray, interpret: bool | None = None
) -> np.ndarray:
    """Host pack + one segmented-kernel launch per tree chunk over that
    chunk's row span.  Returns the (N, C) / (N,) aggregate in original
    request order."""
    from ..kernels.tree_predict.tree_predict import (
        forest_predict_agg_segmented,
    )

    block_trees = plan.engine.block_trees
    block_obs = plan.engine.block_obs
    with span("serve.pack"):
        tree_pack, max_depth, _seg_trees = pack_host_tiles(
            store, plan.users, block_trees
        )
    feature, threshold, fit, is_internal, tree_seg = tree_pack
    n_classes = _n_classes(store)
    n, c_out = plan.n_rows, max(n_classes, 1)
    t = feature.shape[0]

    # Segments only overlap block-diagonally: sort rows by segment and run
    # each tree chunk against just the row span of the users it contains —
    # work stays ~sum_u T_u * N_u instead of T_total * N_total, while one
    # launch still serves several users' trees (the segment mask sorts out
    # chunk-boundary users).  Spans are padded to block_obs multiples (rows)
    # and block_trees (trees) with non-matching sentinel segments, so the
    # jitted kernel sees a handful of distinct shapes, not one per span.
    oseg_s = plan.oseg_s
    with span("serve.prep"):
        xb_s = np.ascontiguousarray(xb[plan.order])
        n_segs = plan.n_users
        seg_start = np.searchsorted(oseg_s, np.arange(n_segs))
        seg_end = np.searchsorted(oseg_s, np.arange(n_segs), side="right")
        total_sorted = np.zeros(
            (n, c_out) if n_classes > 0 else (n,), np.float64
        )
    parts: list[tuple[int, int, object]] = []
    for lo in range(0, t, block_trees):
        hi = min(lo + block_trees, t)
        r0 = int(seg_start[int(tree_seg[lo])])
        r1 = int(seg_end[int(tree_seg[hi - 1])])
        if r1 <= r0:
            continue
        n_rows = r1 - r0
        n_pad = min(-(-n_rows // block_obs) * block_obs, n)
        r1p = min(r0 + n_pad, n)
        r0p = r1p - n_pad  # slide the window instead of materializing pads
        chunk = [tree_seg[lo:hi], feature[lo:hi], threshold[lo:hi],
                 fit[lo:hi], is_internal[lo:hi]]
        if hi - lo < block_trees:  # pad tail chunk to the common tree shape
            pad_t = block_trees - (hi - lo)
            chunk[0] = np.concatenate(
                [chunk[0], np.full(pad_t, -1, np.int32)]
            )
            for i in range(1, 5):
                chunk[i] = np.concatenate(
                    [chunk[i], np.zeros((pad_t,) + chunk[i].shape[1:],
                                        chunk[i].dtype)]
                )
        tseg_c, feat_c, thr_c, fit_c, inter_c = chunk
        part = forest_predict_agg_segmented(
            xb_s[r0p:r1p],
            oseg_s[r0p:r1p],
            tseg_c,
            feat_c,
            thr_c,
            fit_c,
            inter_c,
            max_depth=max_depth,
            n_classes=n_classes,
            block_trees=block_trees,
            block_obs=block_obs,
            interpret=interpret,
            engine="simple",
        )  # dispatched async; host keeps slicing/submitting
        parts.append((r0p, r1p, part))
    with span("serve.wait"):
        for r0p, r1p, part in parts:
            total_sorted[r0p:r1p] += np.asarray(part, np.float64)
    return _unpermute(plan, total_sorted)


# ---------------------------------------------------------------------------
# pipelined — arena index-gather + ONE double-buffered DMA launch
# ---------------------------------------------------------------------------

def build_pipelined_pack(store, plan: ServePlan) -> PipelinedPack:
    """The gather stage: ensure residency, index-gather the plan's users'
    runs, compute per-row-block chunk ranges.  Memoized by ``PlanCache``
    keyed on the plan signature, validated per user (registry version +
    arena run token)."""
    from ..kernels.tree_predict.tree_predict import segment_chunk_ranges

    bt = plan.engine.block_trees
    code, fit, tree_seg, counts, max_depth = store.arena_pack(
        list(plan.users), bt
    )
    bo = min(plan.engine.block_obs, plan.n_rows)
    chunk_lo, chunk_hi = segment_chunk_ranges(
        plan.oseg_s, tree_seg, bt, bo
    )
    return PipelinedPack(
        code, fit, tree_seg, counts, max_depth, chunk_lo, chunk_hi, bo
    )


def run_pipelined(
    store,
    plan: ServePlan,
    pack: PipelinedPack,
    xb: np.ndarray,
    interpret: bool | None = None,
) -> np.ndarray:
    """The single double-buffered DMA kernel launch over a (possibly
    cached) gathered pack.  Returns the aggregate in request order."""
    from ..kernels.tree_predict.tree_predict import (
        forest_predict_agg_segmented_packed,
    )

    with span("serve.prep"):
        xb_s = np.ascontiguousarray(xb[plan.order])
    out = forest_predict_agg_segmented_packed(
        xb_s, plan.oseg_s, pack.code, pack.fit, pack.tree_seg,
        pack.chunk_lo, pack.chunk_hi, pack.max_depth, store.arena.tb2,
        n_classes=_n_classes(store),
        block_trees=plan.engine.block_trees, block_obs=pack.block_obs,
        interpret=interpret,
    )
    with span("serve.wait"):
        out = np.asarray(out, np.float64)
    return _unpermute(plan, out)


# ---------------------------------------------------------------------------
# sharded — tree axis partitioned across devices + one psum
# ---------------------------------------------------------------------------

def build_sharded_pack(store, plan: ServePlan) -> ShardedPack:
    """Per-device gathers under one shared width: admit the WHOLE batch
    before any per-shard gather (a later shard's cold admission may grow
    the arena heap width, which would leave earlier shards' gathered
    arrays at a stale narrower width), bin-pack users by tree count, then
    gather each shard with GLOBAL segment ids."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from ..kernels.tree_predict.ops import (
        partition_segments_by_load,
        shard_mesh,
    )
    from ..kernels.tree_predict.tree_predict import segment_chunk_ranges

    bt = plan.engine.block_trees
    n_dev = plan.engine.n_devices
    store.arena_ensure(list(plan.users), bt)
    shards = partition_segments_by_load(plan.seg_trees, n_dev)
    # per-shard users ascend by segment id: sorted rows keep ranges tight
    shards = [sorted(s) for s in shards]
    t_pad = max(
        max(
            (-(-int(plan.seg_trees[s].sum()) // bt) * bt
             for s in map(np.asarray, shards) if len(s)),
            default=bt,
        ),
        bt,
    )
    bo = min(plan.engine.block_obs, plan.n_rows)
    codes, fits, tsegs, los, his = [], [], [], [], []
    max_depth = 0
    for shard in shards:
        shard_users = [plan.users[s] for s in shard]
        code, fit, tseg, _, max_depth = store.arena_pack(
            shard_users, bt, pad_to=t_pad, seg_ids=shard
        )
        lo, hi = segment_chunk_ranges(plan.oseg_s, tseg, bt, bo)
        codes.append(code)
        fits.append(fit)
        tsegs.append(tseg)
        los.append(lo)
        his.append(hi)
    # each shard goes straight to its own mesh device (never stacked on
    # the arena's device first)
    mesh = shard_mesh(n_dev)
    on_mesh = NamedSharding(mesh, PartitionSpec("shard"))

    def place(parts):
        shape = (len(parts),) + tuple(parts[0].shape)
        return jax.make_array_from_single_device_arrays(
            shape, on_mesh,
            [jax.device_put(p[None], dev)
             for p, dev in zip(parts, mesh.devices.flat)],
        )

    return ShardedPack(
        place(codes), place(fits), np.stack(tsegs),
        np.stack(los), np.stack(his), max_depth, bo,
    )


def run_sharded(
    store,
    plan: ServePlan,
    pack: ShardedPack,
    xb: np.ndarray,
    interpret: bool | None = None,
) -> np.ndarray:
    """Per-device pipelined partials + one psum all-reduce."""
    from ..kernels.tree_predict.ops import (
        forest_predict_agg_segmented_sharded,
    )

    with span("serve.prep"):
        xb_s = np.ascontiguousarray(xb[plan.order])
    out = forest_predict_agg_segmented_sharded(
        xb_s, plan.oseg_s, pack.code, pack.fit, pack.tree_seg,
        pack.chunk_lo, pack.chunk_hi, pack.max_depth, store.arena.tb2,
        n_classes=_n_classes(store),
        block_trees=plan.engine.block_trees, block_obs=pack.block_obs,
        interpret=interpret,
    )
    with span("serve.wait"):
        out = np.asarray(out, np.float64)
    return _unpermute(plan, out)
