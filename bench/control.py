"""The control: the program with its traversal kernel's one-hot gather
computed in bfloat16, the precision just below the float32-at-HIGHEST the
configuration states.

The kernel pins ``Precision.HIGHEST`` on that contraction, so no setting
of JAX's default matmul precision reaches it.  ``bf16_gathers`` swaps the
kernel module's ``_two_level_gather`` for one that rounds both operands to
bfloat16 (what the chip's DEFAULT precision does to float32 operands, and
what the CPU does too, so the control fails there as on the chip), and
drops JAX's in-memory caches on entry and exit, so the kernel is traced
anew with it.  A heap of at most 128 slots needs no contraction (the
gather is a lane select), so there the control equals the program.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def bf16_gathers():
    import jax
    import jax.numpy as jnp

    from repro.kernels.tree_predict import tree_predict as tp

    program = tp._two_level_gather

    def gather(tab3, idx):
        n_hi = tab3.shape[1]
        if n_hi == 1:
            return program(tab3, idx)
        oh_hi = jax.nn.one_hot(idx >> tp.LO_BITS, n_hi, dtype=jnp.float32)
        rows = jnp.einsum(
            "tnh,thl->tnl", oh_hi.astype(jnp.bfloat16),
            tab3.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
        )
        lanes = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 2)
        hit = lanes == (idx & (tp.N_LO - 1))[..., None]
        return jnp.where(hit, rows, 0.0).sum(-1)

    tp._two_level_gather = gather
    jax.clear_caches()
    try:
        yield
    finally:
        tp._two_level_gather = program
        jax.clear_caches()
