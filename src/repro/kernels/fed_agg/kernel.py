"""Pallas TPU kernel: weighted federated aggregation  out = Σ_c w_c · u_c.

Tiling: parameters are flattened to (C, D) and blocked (BC, BD); the grid is
(nd, nc) with the client dimension innermost so each output tile accumulates
in a VMEM fp32 scratch across client blocks (grid iterations on TPU are
sequential over the trailing axis, so the scratch carries).  Every block is
2-D, as Mosaic requires of blocks that do not span their array: weights
ride as (BC, 1) column blocks and the output/scratch as (1, BD) rows.  The
reduction is a broadcast multiply plus a sum over the client (sublane)
axis on the VPU, exact fp32 like the XLA reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(w_ref, u_ref, o_ref, acc_ref, *, n_cblocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)          # (BC, 1)
    u = u_ref[...].astype(jnp.float32)          # (BC, BD)
    acc_ref[...] += jnp.sum(w * u, axis=0, keepdims=True)

    @pl.when(j == n_cblocks - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_d", "interpret"))
def fed_agg_pallas(updates: jnp.ndarray, weights: jnp.ndarray,
                   *, block_c: int = 8, block_d: int = 2048,
                   interpret: bool = False) -> jnp.ndarray:
    """updates: (C, D) flattened client tensors; weights: (C,)."""
    C, D = updates.shape
    bc = min(block_c, C)
    bd = min(block_d, D)
    # pad to multiples
    Cp = -(-C // bc) * bc
    Dp = -(-D // bd) * bd
    if (Cp, Dp) != (C, D):
        updates = jnp.pad(updates, ((0, Cp - C), (0, Dp - D)))
        weights = jnp.pad(weights, (0, Cp - C))
    nd, nc = Dp // bd, Cp // bc

    out = pl.pallas_call(
        functools.partial(_agg_kernel, n_cblocks=nc),
        grid=(nd, nc),
        in_specs=[
            pl.BlockSpec((bc, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((bc, bd), lambda i, j: (j, i)),
        ],
        out_specs=pl.BlockSpec((1, bd), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), updates.dtype),
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=interpret,
    )(weights.reshape(Cp, 1), updates)
    return out[0, :D]
