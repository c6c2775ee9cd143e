"""Shared model substrate: param specs, norms, MLPs, rotary embeddings.

Parameters are described by ``ParamSpec`` metadata trees (shape, dtype,
logical axes, init law).  ``init_params`` materializes values;
``abstract_params`` produces ``ShapeDtypeStruct`` stand-ins for the dry-run;
``repro.sharding`` maps logical axes to mesh axes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dtype: Any
    axes: Tuple[Optional[str], ...]   # logical axis names, len == len(shape)
    init: str = "normal"              # normal | zeros | ones | embed
    scale: float = 1.0                # multiplier on the default fan-in scale
    fan_in: Optional[int] = None      # explicit fan-in (contraction size);
                                      # None = shape heuristic (2D/stacked-3D)

    def __post_init__(self):
        assert len(self.axes) == len(self.shape), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def spec_tree_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_spec)


def abstract_params(specs):
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return spec_tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), specs)


def _init_one(spec: ParamSpec, key) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    if spec.init == "embed":
        std = 1.0 * spec.scale
        return (jax.random.normal(key, spec.shape, jnp.float32) * std
                ).astype(spec.dtype)
    # fan-in scaled normal
    if spec.fan_in is not None:
        fan_in = spec.fan_in
    else:
        fan_in = spec.shape[0] if len(spec.shape) >= 2 \
            else max(spec.shape[-1], 1)
        if len(spec.shape) >= 3:   # stacked/layered weights: fan-in is dim -2
            fan_in = spec.shape[-2]
    std = spec.scale / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std
            ).astype(spec.dtype)


def init_params(specs, rng):
    """Materialize a param tree from a spec tree (per-leaf folded rng)."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    vals = [_init_one(s, k) for s, k in zip(leaves, keys)]
    return jax.tree.unflatten(treedef, vals)


def param_axes(specs):
    """Tree of logical-axis tuples, mirroring the param tree."""
    return spec_tree_map(lambda s: s.axes, specs)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_spec(cfg, d: int, layered: Optional[int] = None):
    shape, axes = (d,), ("embed",)
    if layered is not None:
        shape, axes = (layered, d), ("layers", "embed")
    p = {"scale": ParamSpec(shape, cfg_dtype(cfg.param_dtype), axes, "ones")}
    if cfg.norm == "layernorm":
        p["bias"] = ParamSpec(shape, cfg_dtype(cfg.param_dtype), axes, "zeros")
    return p


def apply_norm(p, x, cfg, eps: Optional[float] = None):
    eps = cfg.norm_eps if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(
            jnp.float32)
    else:
        var = (xf ** 2).mean(-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def cfg_dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Dense / MLP
# ---------------------------------------------------------------------------

def dense_spec(cfg, din: int, dout: int, axes, *, bias: bool = False,
               layered: Optional[int] = None, scale: float = 1.0,
               init: str = "normal"):
    dt = cfg_dtype(cfg.param_dtype)
    shape, ax = (din, dout), tuple(axes)
    if layered is not None:
        shape, ax = (layered, din, dout), ("layers",) + tuple(axes)
    out = {"w": ParamSpec(shape, dt, ax, init, scale)}
    if bias:
        bshape = (dout,) if layered is None else (layered, dout)
        bax = (axes[-1],) if layered is None else ("layers", axes[-1])
        out["b"] = ParamSpec(bshape, dt, bax, "zeros")
    return out


def apply_dense(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def mlp_spec(cfg, d: int, d_ff: int, layered: Optional[int] = None,
             in_axis: str = "embed", ff_axis: str = "mlp"):
    p = {}
    if cfg.mlp_act == "silu_glu":
        p["wi"] = dense_spec(cfg, d, d_ff, (in_axis, ff_axis), layered=layered)
        p["wg"] = dense_spec(cfg, d, d_ff, (in_axis, ff_axis), layered=layered)
    else:
        p["wi"] = dense_spec(cfg, d, d_ff, (in_axis, ff_axis), layered=layered)
    p["wo"] = dense_spec(cfg, d_ff, d, (ff_axis, in_axis), layered=layered)
    return p


def apply_mlp(p, x, cfg):
    if cfg.mlp_act == "silu_glu":
        h = jax.nn.silu(apply_dense(p["wi"], x)) * apply_dense(p["wg"], x)
    elif cfg.mlp_act == "gelu":
        h = jax.nn.gelu(apply_dense(p["wi"], x))
    elif cfg.mlp_act == "relu2":
        h = jnp.square(jax.nn.relu(apply_dense(p["wi"], x)))
    else:
        raise ValueError(cfg.mlp_act)
    return apply_dense(p["wo"], h)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float, yarn=None):
    """Inverse frequencies (D/2,); with a ``YarnConfig`` the YaRN blend of
    interpolated and original frequencies over the correction range
    (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    half = head_dim // 2
    base = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
    if yarn is None:
        return base

    def corr_dim(rot):
        return (head_dim * math.log(yarn.original_max_position_embeddings
                                    / (rot * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp              # 1 = original frequency, 0 = scaled
    return (base / yarn.factor * (1.0 - keep) + base * keep
            ).astype(np.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float, yarn=None):
    """x: (..., S, H, D); positions: broadcastable to (..., S).  With a
    ``YarnConfig``, YaRN frequencies and cos/sin scaled by
    mscale(mscale) / mscale(mscale_all_dim)."""
    d = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(d, theta, yarn))      # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                        # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) \
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def shard_act(x, axes, exec_cfg):
    """with_sharding_constraint via logical activation axes.

    "batch" maps to the (pod, data) mesh axes; weight-style axes resolve via
    ``exec_cfg.rules``.  No-op when exec_cfg carries no mesh (smoke tests,
    single-device runs).
    """
    if exec_cfg is None or getattr(exec_cfg, "mesh", None) is None \
            or getattr(exec_cfg, "rules", None) is None:
        return x
    from jax.sharding import NamedSharding
    from repro.sharding.partitioning import fsdp_axes, spec_for_axes
    rules = dict(exec_cfg.rules)
    rules["batch"] = fsdp_axes(exec_cfg.mesh)
    spec = spec_for_axes(tuple(axes), rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(exec_cfg.mesh, spec))
