"""Ahead-of-time compiles of the served kernels for a described TPU v5e
(no chip attached): the TPU compiler refuses here what interpret mode
on the CPU never checks — lane tiling, VMEM budgets, DMA slice
alignment, dot layouts.  Each test asserts that the compiled program
holds a Mosaic kernel (``tpu_custom_call``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.tree_predict.ops import _sharded_program
from repro.kernels.tree_predict.tree_predict import (
    _forest_predict_agg_seg_impl,
    _forest_predict_agg_seg_pipelined_impl,
    select_path,
)
from repro.serving.plan import ENGINE_BLOCKS

TB2 = 64  # 2 * fused threshold base for 32 bins


@pytest.fixture(scope="module")
def topo():
    """A described v5e 2x2 host, with the persistent compilation cache
    off around these compiles (their entries cannot be read back without
    a chip)."""
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _assert_kernel(compiled) -> str:
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize(
    "depth,d,n_classes,t_pad,n",
    [
        (10, 55, 7, 504, 4096),  # the smoke's single forest
        (10, 55, 7, 504, 1),  # ... scoring one row
        (8, 32, 0, 1280, 8192),  # the smoke's regression fleet batch
        (8, 54, 7, 504, 4096),  # the benchmark's forest: the gemm body
        (8, 54, 7, 504, 1),  # ... scoring one row
    ],
)
def test_pipelined_segmented_compiles(one_chip, depth, d, n_classes,
                                      t_pad, n):
    _compile_pipelined(one_chip, depth, d, n_classes, t_pad, n, TB2)


def _compile_pipelined(one_chip, depth, d, n_classes, t_pad, n, tb2):
    """Compile the pipelined kernel at the engine's blocks, with the body
    ``select_path`` picks for these shapes; returns that body."""
    bt, bo = ENGINE_BLOCKS["pipelined"]
    bo = min(bo, n)
    h = (1 << (depth + 1)) - 1
    g = -(-n // bo)
    path = select_path(depth, n_classes, tb2, d, bt, bo)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _forest_predict_agg_seg_pipelined_impl.lower(
        s((n, d), jnp.int32), s((n,), jnp.int32),
        s((t_pad, h), jnp.float32), s((t_pad, h), jnp.float32),
        s((t_pad,), jnp.int32), s((g,), jnp.int32), s((g,), jnp.int32),
        depth, n_classes, bt, bo, tb2, False, path,
    ).compile()
    _assert_kernel(compiled)
    return path


@pytest.mark.parametrize(
    "depth,d,n_classes,n,tb2",
    [
        (8, 384, 7, 4096, 64),  # the widest features depth 8 admits
        (8, 384, 7, 4096, 512),  # ... with bfloat16 operands (255 bins)
        (8, 54, 7, 4096, 512),  # the benchmark's shapes, bfloat16
        (8, 54, 7, 1, 512),  # ... scoring one row
        (8, 54, 7, 37, 64),  # a row block narrower than a lane row
        (8, 54, 7, 37, 512),  # ... bfloat16
        (7, 992, 7, 4096, 64),  # the widest features depth 7 admits
        (3, 1, 2, 4096, 64),  # one feature, eight bottom slots
    ],
)
def test_gemm_body_compiles_at_the_rule_edges(one_chip, depth, d,
                                              n_classes, n, tb2):
    """Every shape ``select_path`` sends to ``gemm`` must compile: at the
    VMEM budget's edge, with either operand type, and at ragged rows."""
    path = _compile_pipelined(one_chip, depth, d, n_classes, 504, n, tb2)
    assert path == "gemm"


def test_simple_segmented_compiles_at_engine_blocks(one_chip):
    bt, bo = ENGINE_BLOCKS["simple"]
    depth, d, n_classes, n = 10, 55, 7, 4096
    h = (1 << (depth + 1)) - 1

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _forest_predict_agg_seg_impl.lower(
        s((n, d), jnp.int32), s((1, n), jnp.int32), s((bt, 1), jnp.int32),
        s((bt, h), jnp.int32), s((bt, h), jnp.int32),
        s((bt, h), jnp.float32), s((bt, h), jnp.bool_),
        depth, n_classes, bt, bo, False,
    ).compile()
    _assert_kernel(compiled)


def test_sharded_program_compiles_on_2x2(topo):
    # a regression fleet: the walk body
    _compile_sharded(topo, 8, 32, 0, 8192, 320, "walk")


def test_sharded_gemm_program_compiles_on_2x2(topo):
    # the benchmark's classification forest over four chips: the gemm body
    _compile_sharded(topo, 8, 54, 7, 4096, 128, "gemm")


def _compile_sharded(topo, depth, d, n_classes, n, t_pad, want):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(topo.devices), ("shard",))
    n_dev = mesh.devices.size
    bt, bo = ENGINE_BLOCKS["sharded"]
    path = select_path(depth, n_classes, TB2, d, bt, bo)
    assert path == want
    h = (1 << (depth + 1)) - 1
    g = n // bo
    rep, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("shard"))
    args = [
        jax.ShapeDtypeStruct((n, d), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((n_dev, t_pad, h), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n_dev, t_pad, h), jnp.float32, sharding=shard),
        jax.ShapeDtypeStruct((n_dev, t_pad), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n_dev, g), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((n_dev, g), jnp.int32, sharding=shard),
    ]
    program = _sharded_program(
        mesh, depth, n_classes, bt, bo, TB2, False, path
    )
    text = _assert_kernel(jax.jit(program).lower(*args).compile())
    assert "all-reduce" in text
