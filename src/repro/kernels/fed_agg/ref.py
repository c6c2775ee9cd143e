"""Pure-jnp oracle for weighted federated aggregation."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fed_agg_ref(updates: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """out[d...] = Σ_c weights[c] · updates[c, d...]   (fp32 accumulate).

    updates: (C, ...) stacked client tensors; weights: (C,).  HIGHEST
    precision keeps the TPU's MXU from rounding the fp32 operands to
    bfloat16, so this path and the Pallas kernel agree to fp32 rounding.
    """
    C = updates.shape[0]
    flat = updates.reshape(C, -1).astype(jnp.float32)
    out = jnp.einsum("c,cd->d", weights.astype(jnp.float32), flat,
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(updates.shape[1:]).astype(updates.dtype)
