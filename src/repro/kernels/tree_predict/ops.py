"""jit'd wrappers: ForestModel-level prediction via the Pallas kernels,
plus the multi-device sharded entry for the segmented serving kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...runtime.trace import span
from .tree_predict import forest_predict, forest_predict_agg


def predict_forest_kernel(model, x_raw: np.ndarray, interpret: bool | None = None):
    """Ensemble prediction matching repro.forest.predict_forest, but through
    the fused-aggregation Pallas kernel (votes / fit sums are reduced
    in-kernel across the tree-tile grid axis). Returns (n,) predictions."""
    xb = jnp.asarray(model.binner.transform(x_raw), jnp.int32)
    cfg = model.cfg
    if cfg.task == "classification":
        # per-tree argmax class encoded as scalar fit
        fit = jnp.asarray(model.node_fit.argmax(-1), jnp.float32)
        votes = forest_predict_agg(
            xb,
            jnp.asarray(model.feature),
            jnp.asarray(model.threshold),
            fit,
            jnp.asarray(model.is_internal),
            max_depth=cfg.max_depth,
            n_classes=cfg.n_classes,
            interpret=interpret,
        )  # (N, C)
        return np.asarray(votes.argmax(-1))
    fit = jnp.asarray(model.node_fit[..., 0], jnp.float32)
    sums = forest_predict_agg(
        xb,
        jnp.asarray(model.feature),
        jnp.asarray(model.threshold),
        fit,
        jnp.asarray(model.is_internal),
        max_depth=cfg.max_depth,
        interpret=interpret,
    )  # (N,)
    return np.asarray(sums / model.n_trees)


def predict_forest_kernel_per_tree(
    model, x_raw: np.ndarray, interpret: bool | None = None
):
    """(T, N) per-tree leaf fits through the unaggregated kernel (kept for
    sigma^2-style per-tree diagnostics and as a parity reference)."""
    xb = jnp.asarray(model.binner.transform(x_raw), jnp.int32)
    cfg = model.cfg
    if cfg.task == "classification":
        fit = jnp.asarray(model.node_fit.argmax(-1), jnp.float32)
    else:
        fit = jnp.asarray(model.node_fit[..., 0], jnp.float32)
    return forest_predict(
        xb,
        jnp.asarray(model.feature),
        jnp.asarray(model.threshold),
        fit,
        jnp.asarray(model.is_internal),
        max_depth=cfg.max_depth,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Sharded ragged tree axis (ISSUE 3 tentpole piece 3)
# ---------------------------------------------------------------------------

def partition_segments_by_load(
    seg_trees: np.ndarray, n_shards: int
) -> list[list[int]]:
    """Greedy bin-pack of segment (user) ids onto ``n_shards`` devices by
    per-segment tree count: heaviest segment first onto the least-loaded
    shard.  Returns one list of segment ids per shard (possibly empty)."""
    seg_trees = np.asarray(seg_trees, np.int64)
    shards: list[list[int]] = [[] for _ in range(n_shards)]
    loads = np.zeros(n_shards, np.int64)
    for s in np.argsort(-seg_trees, kind="stable"):
        k = int(np.argmin(loads))
        shards[k].append(int(s))
        loads[k] += int(seg_trees[s])
    return shards


def estimate_shard_speedup(seg_trees: np.ndarray, n_shards: int) -> float:
    """Predicted sharded-engine speedup for a batch: total tree load over
    the heaviest shard's load under the greedy bin-pack (1.0 = one user
    dominates and sharding buys nothing; ``n_shards`` = perfectly even).
    The serving session's engine cost model compares this against its
    minimum-speedup threshold instead of blindly sharding on any
    multi-device host."""
    seg_trees = np.asarray(seg_trees, np.int64)
    total = int(seg_trees.sum())
    if total == 0 or n_shards <= 1:
        return 1.0
    shards = partition_segments_by_load(seg_trees, n_shards)
    max_load = max(
        (sum(int(seg_trees[s]) for s in shard) for shard in shards if shard),
        default=total,
    )
    return total / max(max_load, 1)


@functools.lru_cache(maxsize=None)
def shard_mesh(n_devices: int):
    """The 1-D ``("shard",)`` mesh over the first ``n_devices`` devices
    that the sharded engine's tree shards are placed on and run over."""
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:n_devices]), ("shard",))


def _sharded_program(
    mesh, max_depth: int, n_classes: int, block_trees: int,
    block_obs: int, tb2: int, interpret: bool, path: str,
):
    """The shard_map program over ``mesh``: each device runs the pipelined
    segmented kernel on ITS tree shard against the full replicated batch,
    then the (N, C) partials all-reduce."""
    from jax.sharding import PartitionSpec as P

    from .tree_predict import _forest_predict_agg_seg_pipelined_impl

    def per_device(xb, oseg, code, fit, tseg, chunk_lo, chunk_hi):
        part = _forest_predict_agg_seg_pipelined_impl(
            xb, oseg, code[0], fit[0], tseg[0], chunk_lo[0], chunk_hi[0],
            max_depth, n_classes, block_trees, block_obs, tb2, interpret,
            path,
        )
        if n_classes == 0:
            part = part[:, None]
        return jax.lax.psum(part, "shard")

    return jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(
            P(), P(), P("shard"), P("shard"), P("shard"), P("shard"),
            P("shard"),
        ),
        out_specs=P(),
        check_vma=False,  # pallas_call has no replication rule
    )


@functools.lru_cache(maxsize=None)
def _sharded_callable(n_devices: int, *static):
    """The jitted ``_sharded_program`` over ``shard_mesh(n_devices)``, built
    once per static config."""
    return jax.jit(_sharded_program(shard_mesh(n_devices), *static))


def forest_predict_agg_segmented_sharded(
    xb,  # (N, d) int32, replicated
    obs_seg,  # (N,) int32, replicated
    code,  # (S, T_pad, H) float32 fused tiles, one tree shard per device
    fit,  # (S, T_pad, H) float32
    tree_seg,  # (S, T_pad) int32, -1 marks padding trees
    chunk_lo,  # (S, ceil(N / block_obs)) int32 per-shard fori_loop bounds
    chunk_hi,  # (S, ceil(N / block_obs)) int32
    max_depth: int,
    tb2: int,
    n_classes: int = 0,
    block_trees: int = 8,
    block_obs: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Multi-device ragged serving: the tree axis is SHARDED across devices
    (one stacked shard per device, load-balanced by
    ``partition_segments_by_load``), observations are replicated, each
    device accumulates partial votes/sums over its own trees through the
    pipelined DMA kernel, and the (N, C) aggregate all-reduces with one
    ``psum`` — fleets whose hot tree set exceeds one core's VMEM/HBM scale
    out instead of thrashing.

    Vote counts stay integer-exact under the reduction (float32 holds
    integers exactly below 2**24), so classification results are bit-exact
    against the single-device engines."""
    from .tree_predict import (
        _F32_EXACT_INT,
        _int32,
        _validate_f32_exact,
        select_path,
    )

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    s = code.shape[0]
    n_dev = len(jax.devices())
    if s > n_dev:
        raise ValueError(f"{s} tree shards but only {n_dev} devices")
    n, d = xb.shape
    # same guards as the single-device packed entry: out-of-range values
    # must raise, not silently round through the float32 one-hot gathers
    if n_classes > 0 and n_classes >= _F32_EXACT_INT:
        raise ValueError("n_classes >= 2**24 overflows float32 vote counts")
    arrays = {"xb": xb} if isinstance(xb, np.ndarray) else {}
    with span("serve.prep"):
        _validate_f32_exact(max_depth, d, **arrays)
    block_obs = min(block_obs, n)
    path = select_path(max_depth, n_classes, tb2, d, block_trees, block_obs)
    fn = _sharded_callable(
        s, max_depth, n_classes, block_trees, block_obs, int(tb2),
        interpret, path,
    )
    with span("tree_predict.upload"):
        args = jax.device_put([
            _int32(xb), _int32(obs_seg), code, fit, _int32(tree_seg),
            _int32(chunk_lo), _int32(chunk_hi),
        ])
    with span("tree_predict.launch", path=path):
        out = fn(*args)
    return out[:, 0] if n_classes == 0 else out
