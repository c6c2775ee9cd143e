"""Public jit'd wrapper: aggregate a whole pytree of stacked client updates."""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.fed_agg.kernel import fed_agg_pallas
from repro.kernels.fed_agg.ref import fed_agg_ref


def interpret_flag(impl: str, kernel: str = "fed_agg") -> bool:
    """The Pallas ``interpret`` flag for a kernel ``impl`` other than xla.

    The interpreter is a CPU test tool: on a TPU backend
    ``"pallas_interpret"`` raises instead of quietly running it there.
    """
    if impl not in ("pallas", "pallas_interpret"):
        raise ValueError(f"unknown {kernel} impl: {impl!r}")
    if impl == "pallas_interpret" and jax.default_backend() == "tpu":
        raise ValueError(f"{kernel} impl 'pallas_interpret' runs the Pallas "
                         f"interpreter; on a TPU use 'pallas'")
    return impl == "pallas_interpret"


def fed_agg(updates: jnp.ndarray, weights: jnp.ndarray, *,
            impl: str = "pallas_interpret", block_c: int = 8,
            block_d: int = 2048) -> jnp.ndarray:
    """Σ_c w_c · u_c for one stacked tensor (C, ...)."""
    C = updates.shape[0]
    shape = updates.shape[1:]
    if impl == "xla":
        return fed_agg_ref(updates, weights)
    flat = updates.reshape(C, -1)
    out = fed_agg_pallas(flat, weights, block_c=block_c, block_d=block_d,
                         interpret=interpret_flag(impl))
    return out.reshape(shape).astype(updates.dtype)


def fed_agg_tree(updates_tree: Any, weights: jnp.ndarray,
                 **kw) -> Any:
    """Aggregate every leaf of a stacked client-update pytree."""
    return jax.tree.map(lambda u: fed_agg(u, weights, **kw), updates_tree)


def fed_agg_packed(updates: jnp.ndarray, weights: jnp.ndarray, *,
                   impl: str = "xla", block_c: int = 8,
                   block_d: int = 2048) -> jnp.ndarray:
    """Σ_c w_c · u_c over an already-packed (C, D) buffer -> (D,).

    The packed buffer holds ALL leaves of a stacked client pytree
    (``repro.core.aggregation.pack_stacked``), so one call aggregates the
    whole model.  impl: "xla" | "pallas" | "pallas_interpret".
    """
    if impl == "xla":
        return fed_agg_ref(updates, weights)
    return fed_agg_pallas(updates, weights, block_c=block_c,
                          block_d=block_d, interpret=interpret_flag(impl))


def fed_agg_packed_sharded(updates: jnp.ndarray, weights: jnp.ndarray, *,
                           mesh: Mesh, axis: str = "clients",
                           impl: str = "xla", block_c: int = 8,
                           block_d: int = 2048) -> jnp.ndarray:
    """``fed_agg_packed`` over a client-sharded (C, D) buffer -> (D,).

    shard_map over the ``axis`` mesh axis: every device runs the chosen
    single-device impl (xla einsum | pallas | pallas_interpret) on its
    *local* (C/k, D) block of clients — the Pallas kernel therefore never
    sees a partitioned operand, which GSPMD could not guarantee — and the
    fp32 partial weighted sums combine with one ``psum``.  The result is
    replicated (P()) so the surrounding unpack stays device-local.

    Weights must already be normalized globally (Σw = 1 across ALL
    clients); each shard contributes w_local · u_local unscaled.
    """
    if impl != "xla":
        interpret_flag(impl)

    def partial_sum(w_blk, u_blk):
        # per-shard partial Σ_c w_c·u_c in fp32, then one cross-shard psum
        part = fed_agg_packed(u_blk.astype(jnp.float32),
                              w_blk.astype(jnp.float32), impl=impl,
                              block_c=block_c, block_d=block_d)
        return jax.lax.psum(part.astype(jnp.float32), axis)

    return jax.shard_map(partial_sum, mesh=mesh,
                         in_specs=(P(axis), P(axis, None)),
                         out_specs=P(),
                         check_vma=False)(weights, updates)
