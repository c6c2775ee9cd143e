"""Mixture-of-Experts block: top-k router + GShard-style capacity dispatch.

Expert-parallel sharding: the ``expert`` logical axis maps to the ``model``
mesh axis when num_experts is divisible by it (deepseek-v2: 160 experts), else
experts are replicated and the ``expert_mlp`` axis is sharded (mixtral: 8
experts).  Dispatch/combine einsums lower to all-to-alls under pjit when the
token and expert axes live on different mesh axes.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.layers import shard_act


def moe_spec(cfg, layered: Optional[int] = None):
    m = cfg.moe
    d = cfg.d_model
    eff = m.expert_d_ff or cfg.d_ff
    dt = L.cfg_dtype(cfg.param_dtype)
    glu = cfg.mlp_act == "silu_glu"

    def w(shape, axes, init="normal"):
        if layered is not None:
            shape = (layered,) + shape
            axes = ("layers",) + axes
        return L.ParamSpec(shape, dt, axes, init)

    p = {
        "router": w((d, m.num_experts), ("embed", "expert_gate")),
        "wi": w((m.num_experts, d, eff), ("expert", "embed", "expert_mlp")),
        "wo": w((m.num_experts, eff, d), ("expert", "expert_mlp", "embed")),
    }
    if glu:
        p["wg"] = w((m.num_experts, d, eff),
                    ("expert", "embed", "expert_mlp"))
    if m.num_shared_experts:
        sff = (m.shared_d_ff or eff) * m.num_shared_experts
        p["shared"] = L.mlp_spec(cfg, d, sff, layered=layered,
                                 ff_axis="mlp")
    return p


def _act(cfg, h, g=None):
    if cfg.mlp_act == "silu_glu":
        return jax.nn.silu(h) * g
    if cfg.mlp_act == "gelu":
        return jax.nn.gelu(h)
    return jnp.square(jax.nn.relu(h))


def moe_forward(p, x, cfg, exec_cfg=None):
    """x: (B, S, d) -> (B, S, d), plus aux load-balance loss.

    GShard-style grouped dispatch: tokens are split into G groups (one per
    data shard / FL silo), each with a *local* expert capacity
    C' = T'·k/E·cf.  Dispatch tensors are (G, T', E, C') — G× smaller than
    the ungrouped form (which peaked at 21 GB/device on mixtral train_4k) —
    and the expert einsum lowers to the canonical all-to-all when groups
    live on the data axis and experts on the model axis.  Tokens over local
    capacity are dropped (contribute zero), matching the reference systems.
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    G = getattr(exec_cfg, "moe_groups", 1) if exec_cfg is not None else 1
    # auto-scale groups so T' stays bounded: dispatch/expert buffers are
    # O(T'·k·cf) per group — unbounded T' (e.g. 1M-token prefill) blew the
    # einsum dispatch up to 30 TB/device (EXPERIMENTS.md §Perf mixtral it.1)
    G = max(G, T // 4096)
    while T % G != 0:
        G -= 1
    Tl = T // G
    dt = x.dtype
    xt = x.reshape(G, Tl, d)
    dispatch_impl = getattr(exec_cfg, "moe_dispatch", "gather") \
        if exec_cfg is not None else "gather"

    logits = jnp.einsum("gtd,de->gte", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                   # (G, T', E)

    gate_vals, gate_idx = jax.lax.top_k(probs, m.top_k)       # (G, T', k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    E = m.num_experts
    cap = int(max(1, round(Tl * m.top_k / E * m.capacity_factor)))
    if Tl <= 128:
        # decode / tiny batches: full capacity (drops would corrupt the
        # single-token step; cost is negligible at this size)
        cap = Tl

    # position of each (token, slot) within its expert queue (per group)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)     # (G,T',k,E)
    flat = onehot.reshape(G, Tl * m.top_k, E)
    pos = jnp.cumsum(flat, axis=1) - 1                        # (G,T'k,E)
    pos = (pos * flat).sum(-1).reshape(G, Tl, m.top_k)        # (G,T',k)
    keep = pos < cap

    if dispatch_impl == "gather":
        # sort-free gather/scatter dispatch: O(T·k·d) data movement, zero
        # matmul flops — replaces the O(T·E·C·d) one-hot einsums that
        # dominated the MoE rooflines (beyond-paper optimization; see
        # EXPERIMENTS.md §Perf deepseek/mixtral iterations).
        g_ids = jnp.arange(G)[:, None, None]
        tok_ids = jnp.broadcast_to(jnp.arange(Tl)[None, :, None],
                                   (G, Tl, m.top_k))
        safe_pos = jnp.where(keep, pos, cap)          # overflow slot
        # slot tables (G, E, C'+1): token index + validity per expert slot
        idx = jnp.zeros((G, E, cap + 1), jnp.int32).at[
            g_ids, gate_idx, safe_pos].set(tok_ids.astype(jnp.int32),
                                           mode="drop")[..., :cap]
        slot_ok = jnp.zeros((G, E, cap + 1), bool).at[
            g_ids, gate_idx, safe_pos].set(True, mode="drop")[..., :cap]
        # gather expert inputs: (G, E, C', d) -> (E, G, C', d)
        xin = jnp.take_along_axis(
            xt, idx.reshape(G, E * cap)[..., None], axis=1
        ).reshape(G, E, cap, d) * slot_ok[..., None].astype(dt)
        xin = jnp.swapaxes(xin, 0, 1)
    else:
        # reference one-hot einsum dispatch (GShard formulation)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                                dtype=dt)[..., :cap]          # (G,T',k,C')
        disp = jnp.einsum("gtke,gtkc->gtec", onehot.astype(dt), pos_oh)
        xin = jnp.einsum("gtec,gtd->egcd", disp, xt)

    xin = shard_act(xin, ("expert", "batch", None, None), exec_cfg)
    h = jnp.einsum("egcd,edf->egcf", xin, p["wi"].astype(dt))
    g = (jnp.einsum("egcd,edf->egcf", xin, p["wg"].astype(dt))
         if "wg" in p else None)
    h = _act(cfg, h, g)
    h = shard_act(h, ("expert", "batch", None, "expert_mlp"), exec_cfg)
    eout = jnp.einsum("egcf,efd->egcd", h, p["wo"].astype(dt))
    eout = shard_act(eout, ("expert", "batch", None, None), exec_cfg)

    if dispatch_impl == "gather":
        # combine: gather each (token, k) slot's expert output
        flat = jnp.swapaxes(eout, 0, 1).reshape(G, E * cap, d)
        slot = (gate_idx * cap + safe_pos).reshape(G, Tl * m.top_k)
        vals = jnp.take_along_axis(
            flat, jnp.minimum(slot, E * cap - 1)[..., None], axis=1
        ).reshape(G, Tl, m.top_k, d)
        w_tk = (gate_vals * keep).astype(jnp.float32)
        out = jnp.einsum("gtkd,gtk->gtd", vals.astype(jnp.float32),
                         w_tk).astype(dt)
    else:
        comb = jnp.einsum("gtke,gtkc,gtk->gtec",
                          onehot.astype(jnp.float32),
                          pos_oh.astype(jnp.float32),
                          gate_vals * keep).astype(dt)
        out = jnp.einsum("gtec,egcd->gtd", comb, eout)

    if m.num_shared_experts:
        out = out + L.apply_mlp(p["shared"], xt, cfg)

    # load-balance aux loss (Switch-style): E * sum_e f_e * P_e
    frac = onehot[:, :, 0, :].astype(jnp.float32).mean((0, 1))
    pmean = probs.mean((0, 1))
    aux = E * jnp.sum(frac * pmean) * m.router_aux_weight
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel share: the experts one chip holds, dropless
# ---------------------------------------------------------------------------

def held_moe_spec(cfg, layered: Optional[int] = None):
    """One chip's share of an expert-parallel MoE layer: the router over
    all ``num_experts``, the ``experts_held`` routed experts it holds and
    the shared experts (no capacity: the share is dropless)."""
    m = cfg.moe
    held = m.experts_held or m.num_experts
    d, eff = cfg.d_model, m.expert_d_ff or cfg.d_ff
    dt = L.cfg_dtype(cfg.param_dtype)

    def w(shape, axes):
        if layered is not None:
            shape, axes = (layered,) + shape, ("layers",) + axes
        return L.ParamSpec(shape, dt, axes, "normal")

    p = {"router": w((d, m.num_experts), ("embed", "expert_gate")),
         "wi": w((held, d, eff), ("expert", "embed", "expert_mlp")),
         "wg": w((held, d, eff), ("expert", "embed", "expert_mlp")),
         "wo": w((held, eff, d), ("expert", "expert_mlp", "embed"))}
    if m.num_shared_experts:
        p["shared"] = L.mlp_spec(
            cfg, d, (m.shared_d_ff or eff) * m.num_shared_experts,
            layered=layered, ff_axis="mlp")
    return p


def route(router_w, x, cfg):
    """Softmax router over every expert: ``(gates, experts)``, each
    (T, top_k), gates in float32 and not renormalised over the top k
    (DeepSeek-V2-Lite: ``norm_topk_prob`` false, scaling factor 1)."""
    logits = jnp.einsum("td,de->te", x, router_w.astype(x.dtype),
                        preferred_element_type=jnp.float32)
    return jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.moe.top_k)


def held_moe_forward(p, x, cfg, first_expert: int = 0,
                     with_shared: bool = True):
    """x: (T, d) -> ((T, d), load): this chip's part of the MoE layer.

    The router scores all ``num_experts``; of each token's top-k
    (token, slot) pairs those that land on the held experts
    ``[first_expert, first_expert + experts_held)`` are sorted by expert
    and run through ``jax.lax.ragged_dot`` (every pair, none dropped), the
    rest contribute nothing here (another chip holds their expert).  The
    shared experts run on every token once (``with_shared=False`` leaves
    them to another share).  ``load`` is the (experts_held,) count of
    pairs each held expert took."""
    m = cfg.moe
    held = p["wi"].shape[0]
    T, d = x.shape
    k = m.top_k
    dt = x.dtype
    with jax.named_scope("moe.route"):
        gates, experts = route(p["router"], x, cfg)
        local = experts.reshape(-1) - first_expert            # (T*k,)
        mine = (local >= 0) & (local < held)
        key = jnp.where(mine, local, held)
        order = jnp.argsort(key, stable=True)
        tok = order // k
        sizes = jnp.zeros((held,), jnp.int32).at[
            jnp.where(mine, local, held)].add(1, mode="drop")
        w_pair = jnp.where(mine, gates.reshape(-1), 0.0)[order]
        keep = mine[order]
    with jax.named_scope("moe.experts"):
        # rows past the held groups are never written by the grouped
        # product, nor by its transpose in the backward pass: select, do
        # not multiply, on the way out and (for the input gradient the
        # gather scatters back) on the way in, so nothing stale leaks in
        xs = jnp.where(keep[:, None], jnp.take(x, tok, axis=0), 0)
        h = jax.lax.ragged_dot(xs, p["wi"].astype(dt), sizes)
        g = jax.lax.ragged_dot(xs, p["wg"].astype(dt), sizes)
        y = jax.lax.ragged_dot(_act(cfg, h, g), p["wo"].astype(dt), sizes)
        y = jnp.where(keep[:, None], y.astype(jnp.float32)
                      * w_pair[:, None], 0.0)
        out = jnp.zeros((T, d), jnp.float32).at[tok].add(y)
    if m.num_shared_experts and with_shared:
        with jax.named_scope("moe.shared"):
            out = out + L.apply_mlp(p["shared"], x, cfg).astype(jnp.float32)
    return out.astype(dt), sizes
