"""Low-rank adapters over a frozen MLA + MoE decoder trunk.

The model fleet clients fine-tune in federated instruction tuning (FedIT,
arXiv 2305.05644; OpenFedLLM, arXiv 2402.06954): every client trains its
own LoRA adapters (``LoRAConfig``) while the trunk stays frozen and is
shared by the whole cohort.  The trunk is one chip's share of a
DeepSeek-V2 deployment (``ModelConfig`` with ``moe.experts_held``):
``moe.first_k_dense_replace`` dense layers, then MoE layers whose router
scores every expert while only the held experts run here
(``moe.held_moe_forward``).

Trees.  ``trunk``: ``{"embed": (V, d), "final_norm": {"scale"},
"layers": [{"attn": mla_spec, "attn_norm", "mlp" | "moe", "mlp_norm"},
...], "lm_head": (d, V)}`` in ``param_dtype``.  ``adapters``:
``{"layers": [{target: {"a": (in, r), "b": (r, out)}}, ...]}`` in float32
for ``target`` in ``lora_dims`` (``q`` = q_proj, ``kv_a`` =
kv_a_proj_with_mqa, ``kv_b`` = kv_b_proj, ``o`` = o_proj): the projection
``x W`` becomes ``x W + (alpha / r) (x a) b``.

Initial values.  Every leaf draws from ``fold_in(key(seed + 1),
crc32(name))``, ``name`` its "/"-joined tree path, so a leaf's value does
not depend on the others: trunk matrices N(0, 0.02^2) (the published
``initializer_range``) rounded to bfloat16 (whatever ``param_dtype``
holds them), norm scales 1; adapter
``a`` N(0, 1 / in), ``b`` zero (LoRA's initialisation: the adapted model
starts as the trunk).

Precision.  Activations enter every trunk matrix product in the trunk's
dtype (bfloat16: float32 accumulation on the MXU); the residual stream,
norms, softmax, router gates, adapter products (at ``HIGHEST``) and the
loss are float32.  Each layer is rematerialised in the backward pass.

Batched over a cohort.  ``cohort_losses`` takes adapters with a leading
client axis (C, ...) and tokens (C, b, S): the trunk's products run over
all C * b sequences at once and each client's adapters over its own
rows, so the MoE routes the whole cohort's tokens together (routing is
per token, so this equals routing each client alone).  The gradient of
the summed losses with respect to the stacked adapters is each client's
own gradient.
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02


# ---------------------------------------------------------------------------
# Trees and initial values
# ---------------------------------------------------------------------------

def trunk_spec(cfg) -> Dict[str, Any]:
    dt = L.cfg_dtype(cfg.param_dtype)
    dense = cfg.moe.first_k_dense_replace if cfg.moe is not None \
        else cfg.num_layers
    layers = []
    for i in range(cfg.num_layers):
        lp = {"attn_norm": L.norm_spec(cfg, cfg.d_model),
              "attn": A.mla_spec(cfg),
              "mlp_norm": L.norm_spec(cfg, cfg.d_model)}
        if i < dense:
            lp["mlp"] = L.mlp_spec(cfg, cfg.d_model, cfg.d_ff)
        else:
            lp["moe"] = M.held_moe_spec(cfg)
        layers.append(lp)
    return {"embed": L.ParamSpec((cfg.vocab_size, cfg.d_model), dt,
                                 ("vocab", "embed")),
            "final_norm": L.norm_spec(cfg, cfg.d_model),
            "layers": layers,
            "lm_head": L.ParamSpec((cfg.d_model, cfg.vocab_size), dt,
                                   ("embed", "vocab"))}


def lora_dims(cfg) -> Dict[str, Tuple[int, int]]:
    """(in, out) of each adaptable projection, as HF's nn.Linear sees it."""
    m, h = cfg.mla, cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {"q": (cfg.d_model, h * qk),
            "kv_a": (cfg.d_model, m.kv_lora_rank + m.qk_rope_head_dim),
            "kv_b": (m.kv_lora_rank, h * (m.qk_nope_head_dim
                                          + m.v_head_dim)),
            "o": (h * m.v_head_dim, cfg.d_model)}


def adapter_shapes(cfg, lora) -> Dict[str, Any]:
    dims = lora_dims(cfg)
    return {"layers": [{t: {"a": (dims[t][0], lora.rank),
                            "b": (lora.rank, dims[t][1])}
                        for t in dims}
                       for _ in range(cfg.num_layers)]}


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def leaf_key(seed: int, name: str):
    return jax.random.fold_in(jax.random.key(int(seed) + 1),
                              zlib.crc32(name.encode()))


def init_trunk(cfg, seed: int):
    def one(path, spec):
        if spec.init == "ones":
            return jnp.ones(spec.shape, spec.dtype)
        return (jax.random.normal(leaf_key(seed, path_name(path)),
                                  spec.shape, jnp.float32)
                * INIT_STD).astype(jnp.bfloat16).astype(spec.dtype)

    return jax.tree_util.tree_map_with_path(one, trunk_spec(cfg),
                                            is_leaf=L.is_spec)


def init_adapters(cfg, lora, seed: int):
    def one(path, shape):
        name = path_name(path)
        if name.endswith("/b"):
            return jnp.zeros(shape, jnp.float32)
        return jax.random.normal(leaf_key(seed, name), shape,
                                 jnp.float32) / np.sqrt(shape[0])

    return jax.tree_util.tree_map_with_path(
        one, adapter_shapes(cfg, lora),
        is_leaf=lambda s: isinstance(s, tuple))


def named_leaves(tree) -> List[Tuple[str, Any]]:
    """(name, leaf) in flattening order."""
    return [(path_name(p), leaf) for p, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _adapter(layer_ad, C: int, scale: float):
    """The ``adapter`` callable of ``attention.mla_forward`` for one layer:
    client c's rows of the (C * b, S, in) input through its own (a, b)."""
    def apply(name, inp):
        a, b = layer_ad[name]["a"], layer_ad[name]["b"]
        z = inp.reshape(C, -1, inp.shape[-1]).astype(jnp.float32)
        u = jnp.einsum("cti,cir->ctr", z, a, precision=HIGHEST)
        v = jnp.einsum("ctr,cro->cto", u, b, precision=HIGHEST) * scale
        return v.reshape(inp.shape[:-1] + (b.shape[-1],))

    return apply


def _layer(cfg, lora, C: int):
    cdt = L.cfg_dtype(cfg.param_dtype)

    def fwd(resid, lp, layer_ad):
        S = resid.shape[1]
        pos = jnp.broadcast_to(jnp.arange(S)[None], resid.shape[:2])
        h = L.apply_norm(lp["attn_norm"], resid, cfg).astype(cdt)
        resid = resid + A.mla_forward(
            lp["attn"], h, pos, cfg, impl="dense",
            adapter=_adapter(layer_ad, C, lora.scale)).astype(jnp.float32)
        h = L.apply_norm(lp["mlp_norm"], resid, cfg).astype(cdt)
        if "moe" in lp:
            y, load = M.held_moe_forward(lp["moe"],
                                         h.reshape(-1, h.shape[-1]), cfg)
            y = y.reshape(h.shape)
        else:
            y, load = L.apply_mlp(lp["mlp"], h, cfg), None
        return resid + y.astype(jnp.float32), load

    return fwd


def hidden_states(adapters, trunk, tokens, cfg, lora):
    """(C, b, S) tokens -> ((C * b, S, d) float32 final hidden states,
    [per MoE layer (experts_held,) pair counts])."""
    C, b, S = tokens.shape
    cdt = L.cfg_dtype(cfg.param_dtype)
    resid = jnp.take(trunk["embed"], tokens.reshape(C * b, S), axis=0) \
        .astype(jnp.float32)
    fwd = jax.checkpoint(_layer(cfg, lora, C))
    loads = []
    for lp, la in zip(trunk["layers"], adapters["layers"]):
        resid, load = fwd(resid, lp, la)
        if load is not None:
            loads.append(load)
    return L.apply_norm(trunk["final_norm"], resid, cfg).astype(cdt), loads


def _head_ce(h, w, targets):
    """Per-token cross-entropy (and argmax hits) of the LM head."""
    with jax.named_scope("lm_head"):
        logits = jnp.einsum("nsd,dv->nsv", h, w,
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
        hit = (jnp.argmax(logits, axis=-1) == targets)
    return lse - tgt, hit


def cohort_losses(adapters, trunk, tokens, targets, cfg, lora):
    """(C,) mean next-token cross-entropy of each client's batch."""
    C, b, S = tokens.shape
    h, _ = hidden_states(adapters, trunk, tokens, cfg, lora)
    ce, _ = jax.checkpoint(_head_ce)(h, trunk["lm_head"],
                                     targets.reshape(C * b, S))
    return ce.reshape(C, b * S).mean(-1)


def value_and_grad(cfg, lora):
    """``fn(adapters (C, ...), trunk, tokens, targets) -> ((C,) losses,
    (C, ...) gradients)``: each client's loss and adapter gradient."""
    def total(ad, trunk, x, y):
        losses = cohort_losses(ad, trunk, x, y, cfg, lora)
        return losses.sum(), losses

    g = jax.value_and_grad(total, has_aux=True)

    def fn(adapters, trunk, x, y):
        (_, losses), grads = g(adapters, trunk, x, y)
        return losses, grads

    return fn


def token_accuracy(adapters, trunk, tokens, targets, cfg, lora):
    """Share of next tokens the model's argmax predicts, over (n, S)."""
    ad = jax.tree.map(lambda a: a[None], adapters)
    h, _ = hidden_states(ad, trunk, tokens[None], cfg, lora)
    _, hit = _head_ce(h, trunk["lm_head"], targets)
    return hit.mean()


def expert_load(adapters, trunk, tokens, cfg, lora):
    """(experts_held,) (token, slot) pairs the held experts took, summed
    over the MoE layers, for (..., S) tokens through the one model
    ``adapters``."""
    ad = jax.tree.map(lambda a: a[None], adapters)
    _, loads = hidden_states(ad, trunk,
                             tokens.reshape(1, -1, tokens.shape[-1]),
                             cfg, lora)
    return sum(loads)
