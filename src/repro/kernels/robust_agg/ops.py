"""Robust reductions over the packed (C, D) aggregation buffer.

``geometric_median`` is the smoothed Weiszfeld iteration (RFA, Pillutla
et al. arXiv 1912.13445) built from two device primitives per step: the
per-client residual-norm kernel (``residual_norms``) and the existing
weighted-sum kernel (``repro.kernels.fed_agg``) — so every ``impl``
(xla | pallas | pallas_interpret) the mean path supports works here too.
The iteration count is static: the loop unrolls into one jit with no
convergence sync.

``*_sharded`` variants run under a ``("clients",)`` mesh via one
shard_map around the whole iteration: distances are shard-local (each
row lives whole on one device), and each Weiszfeld step needs exactly
two fp32 ``psum``s (Σβ_c·u_c and Σβ_c) — zero host syncs, matching the
mean path's collective discipline.  ``trimmed_mean_sharded`` instead
``all_gather``s the client rows and runs the coordinate-wise sort
replicated (a per-coordinate order statistic has no shard-local form);
fine at cohort scale, where the (X, D) buffer is small.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.fed_agg.ops import fed_agg_packed, interpret_flag
from repro.kernels.robust_agg.kernel import residual_norms_pallas

TINY = 1e-30


def residual_norms(updates: jnp.ndarray, center: jnp.ndarray, *,
                   impl: str = "xla", block_c: int = 8,
                   block_d: int = 2048) -> jnp.ndarray:
    """dist_c = ||u_c - z||_2 over a packed (C, D) buffer -> (C,) fp32."""
    if impl == "xla":
        r = updates.astype(jnp.float32) - center.astype(jnp.float32)[None]
        return jnp.sqrt(jnp.sum(r * r, axis=1))
    return residual_norms_pallas(
        updates, center, block_c=block_c, block_d=block_d,
        interpret=interpret_flag(impl, "robust_agg"))


def _weiszfeld_step(updates, w, z, *, eps, impl, block_c, block_d,
                    psum_axis=None):
    """One smoothed Weiszfeld reweighting; ``psum_axis`` makes the two
    reductions (Σβ·u and Σβ) cross-shard."""
    dist = residual_norms(updates, z, impl=impl, block_c=block_c,
                          block_d=block_d)
    beta = jnp.where(w > 0, w / jnp.maximum(dist, eps), 0.0)
    bsum = beta.sum()
    if psum_axis is not None:
        bsum = jax.lax.psum(bsum, psum_axis)
    z = fed_agg_packed(updates, beta / jnp.maximum(bsum, TINY), impl=impl,
                       block_c=block_c, block_d=block_d)
    if psum_axis is not None:
        z = jax.lax.psum(z.astype(jnp.float32), psum_axis)
    return z


def geometric_median(updates: jnp.ndarray, weights: jnp.ndarray, *,
                     iters: int = 6, eps: float = 1e-6, impl: str = "xla",
                     block_c: int = 8, block_d: int = 2048) -> jnp.ndarray:
    """Smoothed Weiszfeld geometric median of (C, D) rows -> (D,) fp32.

    ``weights`` are the (unnormalized) aggregation weights — zero rows
    (clients that did not report) never influence the iteration.  The
    init point is the weighted mean, so ``iters=0`` degrades to the mean
    path exactly.
    """
    w = weights.astype(jnp.float32)
    u = updates.astype(jnp.float32)
    z = fed_agg_packed(u, w / jnp.maximum(w.sum(), TINY), impl=impl,
                       block_c=block_c, block_d=block_d)
    for _ in range(int(iters)):
        z = _weiszfeld_step(u, w, z, eps=eps, impl=impl, block_c=block_c,
                            block_d=block_d)
    return z


def geometric_median_sharded(updates: jnp.ndarray, weights: jnp.ndarray,
                             *, mesh: Mesh, axis: str = "clients",
                             iters: int = 6, eps: float = 1e-6,
                             impl: str = "xla", block_c: int = 8,
                             block_d: int = 2048) -> jnp.ndarray:
    """``geometric_median`` over a client-sharded (C, D) buffer -> (D,).

    One shard_map wraps the whole iteration; the result is replicated
    (P()) like the mean path's psum output.
    """
    def body(w_blk, u_blk):
        w = w_blk.astype(jnp.float32)
        u = u_blk.astype(jnp.float32)
        wsum = jax.lax.psum(w.sum(), axis)
        z = jax.lax.psum(
            fed_agg_packed(u, w / jnp.maximum(wsum, TINY), impl=impl,
                           block_c=block_c, block_d=block_d)
            .astype(jnp.float32), axis)
        for _ in range(int(iters)):
            z = _weiszfeld_step(u, w, z, eps=eps, impl=impl,
                                block_c=block_c, block_d=block_d,
                                psum_axis=axis)
        return z

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis), P(axis, None)),
                         out_specs=P(), check_vma=False)(weights, updates)


def trimmed_mean(updates: jnp.ndarray, weights: jnp.ndarray, *,
                 trim: float = 0.2) -> jnp.ndarray:
    """Coordinate-wise weighted trimmed mean of (C, D) rows -> (D,) fp32.

    Per coordinate, the ``k = floor(trim * m)`` smallest and largest
    values among the ``m`` valid (weight > 0) clients are dropped and
    the survivors average with their weights (``k`` is capped so at
    least one row always survives).  Rank computation is the double
    argsort over the client axis — O(C log C) per coordinate, one fused
    sort kernel for the whole buffer.
    """
    u = updates.astype(jnp.float32)
    w = weights.astype(jnp.float32)
    valid = w > 0
    m = valid.sum()
    k = jnp.minimum(jnp.floor(trim * m + 1e-6).astype(jnp.int32),
                    jnp.maximum((m - 1) // 2, 0))
    key = jnp.where(valid[:, None], u, jnp.inf)   # invalid ranks land last
    order = jnp.argsort(key, axis=0)
    ranks = jnp.argsort(order, axis=0)
    keep = valid[:, None] & (ranks >= k) & (ranks < m - k)
    num = (w[:, None] * keep * u).sum(axis=0)
    den = (w[:, None] * keep).sum(axis=0)
    return num / jnp.maximum(den, TINY)


def trimmed_mean_sharded(updates: jnp.ndarray, weights: jnp.ndarray, *,
                         mesh: Mesh, axis: str = "clients",
                         trim: float = 0.2) -> jnp.ndarray:
    """``trimmed_mean`` over a client-sharded buffer -> replicated (D,).

    The per-coordinate order statistics need every client's value, so
    the rows are ``all_gather``ed and the sort runs replicated on each
    device — redundant compute, zero extra syncs.
    """
    def body(w_blk, u_blk):
        wg = jax.lax.all_gather(w_blk, axis, tiled=True)
        ug = jax.lax.all_gather(u_blk, axis, tiled=True)
        return trimmed_mean(ug, wg, trim=trim)

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis), P(axis, None)),
                         out_specs=P(), check_vma=False)(weights, updates)


def masked_median(x: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Lower median of ``x`` over ``valid`` entries (0.0 when none)."""
    m = valid.sum()
    order = jnp.sort(jnp.where(valid, x, jnp.inf))
    i = jnp.clip((m - 1) // 2, 0, x.shape[0] - 1)
    return jnp.where(m > 0, order[i], 0.0)
