"""Pallas TPU kernel: per-client residual norms  dist_c = ||u_c - z||.

The Weiszfeld inner loop needs one (C,) distance vector per iteration —
the only part of the geometric median the weighted-sum kernel
(``repro.kernels.fed_agg``) cannot serve.  Tiling mirrors that kernel
with the roles of the axes swapped: the packed (C, D) buffer is blocked
(BC, BD) and the grid is (nc, nd) with the *parameter* dimension
innermost, so each client block accumulates its squared residuals in a
(BC, 1) VMEM fp32 scratch across D blocks (TPU grid iterations are
sequential over the trailing axis, so the scratch carries) and takes one
sqrt at the flush.  Every block is 2-D, as Mosaic requires of blocks that
do not span their array: the center rides as (1, BD) rows and the output
as (BC, 1) columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dist_kernel(z_ref, u_ref, o_ref, acc_ref, *, n_dblocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    z = z_ref[...].astype(jnp.float32)          # (1, BD)
    u = u_ref[...].astype(jnp.float32)          # (BC, BD)
    r = u - z
    acc_ref[...] += jnp.sum(r * r, axis=1, keepdims=True)

    @pl.when(j == n_dblocks - 1)
    def _done():
        o_ref[...] = jnp.sqrt(acc_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_d", "interpret"))
def residual_norms_pallas(updates: jnp.ndarray, center: jnp.ndarray,
                          *, block_c: int = 8, block_d: int = 2048,
                          interpret: bool = False) -> jnp.ndarray:
    """updates: (C, D) packed client rows; center: (D,) -> (C,) fp32.

    Zero-padding is exact: padded D columns are zero in both operands
    (residual 0), padded client rows are sliced off the output.
    """
    C, D = updates.shape
    bc = min(block_c, C)
    bd = min(block_d, D)
    Cp = -(-C // bc) * bc
    Dp = -(-D // bd) * bd
    if (Cp, Dp) != (C, D):
        updates = jnp.pad(updates, ((0, Cp - C), (0, Dp - D)))
        center = jnp.pad(center, (0, Dp - D))
    nc, nd = Cp // bc, Dp // bd

    out = pl.pallas_call(
        functools.partial(_dist_kernel, n_dblocks=nd),
        grid=(nc, nd),
        in_specs=[
            pl.BlockSpec((1, bd), lambda i, j: (0, j)),
            pl.BlockSpec((bc, bd), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bc, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Cp, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bc, 1), jnp.float32)],
        interpret=interpret,
    )(center.reshape(1, Dp), updates)
    return out[:C, 0]
