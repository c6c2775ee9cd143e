"""DeepSeek-V2-Lite's data, plain reference and counts: fleet clients
fine-tune LoRA adapters over one chip's share of the frozen trunk.

A configuration whose ``model`` block says ``"kind":
"deepseek_v2_lite"`` is trained on this file's data and checked against
this file's model (``harness.MODEL_API``; ``models/mlp.py`` says what
each function does).  The block carries the published config's keys
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json)
with the chip's share in three of them: ``num_hidden_layers`` (the
layers held), ``n_routed_experts`` (the experts held, numbered from
``first_expert``; the router scores ``n_routed_experts *
expert_parallel``) and ``vocab_size`` (the vocabulary's first slice),
and the adapters: ``lora_rank``, ``lora_alpha``, ``lora_targets``.

The model follows HF ``modeling_deepseek.py`` (DeepseekV2ForCausalLM),
in ``jax.numpy`` in the dtype of its leaves (float32 as the
configuration states it; the control hands it bfloat16), matrix products
at ``HIGHEST``:

* RMSNorm in float32 (``rms_norm_eps``), cast back to the leaves' dtype;
* MLA without ``q_lora``: ``q_proj``; ``kv_a_proj_with_mqa`` -> the
  512-wide latent (``kv_a_layernorm``) and a 64-wide rope key shared by
  the heads; ``kv_b_proj`` -> per head 128 key dims then 128 value
  dims; rope on the 64 rope dims of q and k after HF's de-interleave
  (pairs (2i, 2i+1) -> halves), YaRN frequencies and cos/sin times
  mscale(mscale) / mscale(mscale_all_dim) (= 1 here); softmax scale
  192^-1/2 * mscale(40, 0.707)^2; causal softmax in float32;
* the first ``first_k_dense_replace`` layers a SiLU-GLU MLP
  (``intermediate_size``), the others MoE: a softmax router over all
  experts in float32, the top ``num_experts_per_tok`` gates not
  renormalised (``norm_topk_prob: false``) times
  ``routed_scaling_factor``; each held expert's SiLU-GLU output weighted
  by the token's gate for it (zero where the token did not pick it); the
  ``n_shared_experts`` as one MLP of width ``n_shared_experts *
  moe_intermediate_size``; untied ``lm_head``; mean next-token
  cross-entropy over the client's batch.

Departures, each also the program's: experts the chip does not hold add
nothing (``model-configs`` §4: another chip computes them); the logits
and the loss are over the vocabulary slice; the MoE's auxiliary
load-balancing loss (``aux_loss_alpha``, ``seq_aux``) is left out (the
router is frozen); HF's attention dropout is 0 anyway.  Each layer is
rematerialised and attention runs in query blocks of ``ATTN_BLOCK``, so
the whole cohort's reference fits the chip; neither changes the result.

Leaves.  Adapters (``leaf_shapes``): per layer ``i`` and target ``t`` in
``lora_targets`` (``q`` = q_proj, ``kv_a`` = kv_a_proj_with_mqa,
``kv_b`` = kv_b_proj, ``o`` = o_proj) ``layers/i/t/a`` (in, r) and
``layers/i/t/b`` (r, out): ``x W`` becomes ``x W + (alpha / r) x a b``.
Trunk (frozen), HF names: ``embed`` (embed_tokens, (V, d)), ``lm_head``
((d, V)), ``final_norm/scale``; per layer ``attn_norm/scale``
(input_layernorm), ``mlp_norm/scale`` (post_attention_layernorm),
``attn/wq`` (q_proj, (d, h, 192)), ``attn/w_dkv`` (kv_a_proj_with_mqa,
(d, 576)), ``attn/kv_norm`` (kv_a_layernorm), ``attn/w_uk`` and
``attn/w_uv`` (kv_b_proj's key and value halves, (512, h, 128)),
``attn/wo`` (o_proj, (h, 128, d)); dense ``mlp/{wi,wg,wo}/w``
(gate_proj, up_proj, down_proj); MoE ``moe/router`` ((d, E)),
``moe/{wi,wg,wo}`` (the held experts' gate, up, down, stacked) and
``moe/shared/{wi,wg,wo}/w``.

Initial values: each leaf from ``fold_in(key(seed + 1), crc32(name))``:
trunk matrices N(0, 0.02^2) (``initializer_range``) rounded to bfloat16
(the program's trunk dtype, so both hold the same weights), norm scales
1; adapter ``a`` N(0, 1 / in), ``b`` zero.

Data: each client's sequences follow a bigram mixture of its own:
``topics`` seeded successor tables over the vocabulary slice, the client
weighing them by a softmax of seeded normals (``skew``); each sequence
picks one topic from its client's mixture, its first token uniformly
and each next token the topic's successor with probability ``follow``,
else uniformly.  Test sequences weigh the topics alike.  ``x`` holds
positions 0..S-1, ``y`` positions 1..S.
"""
from __future__ import annotations

import functools
import math
import zlib
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INIT_STD = 0.02
ATTN_BLOCK = 128


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

class Data(NamedTuple):
    x: jax.Array            # (N, n_per_client, seq_len) int32
    y: jax.Array            # (N, n_per_client, seq_len) int32
    test_x: jax.Array       # (n_test, seq_len) int32
    test_y: jax.Array       # (n_test, seq_len) int32
    num_classes: int        # the vocabulary slice


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7, 8))
def _make(key, num_clients, n_per_client, seq_len, n_test, vocab, topics,
          follow, skew):
    k_succ, k_mix, k_pick, k_tpick, k_walk, k_twalk = jax.random.split(
        key, 6)
    succ = jax.random.randint(k_succ, (topics, vocab), 0, vocab)
    mix = jax.random.normal(k_mix, (num_clients, topics)) * skew
    topic = jax.random.categorical(
        k_pick, mix[:, None, :], axis=-1,
        shape=(num_clients, n_per_client))
    t_topic = jax.random.randint(k_tpick, (n_test,), 0, topics)

    def walk(key, topic):
        n = topic.shape[0]
        k0, k_steps = jax.random.split(key)
        first = jax.random.randint(k0, (n,), 0, vocab)

        def step(tok, k):
            k_f, k_u = jax.random.split(k)
            nxt = jnp.where(jax.random.uniform(k_f, (n,)) < follow,
                            succ[topic, tok],
                            jax.random.randint(k_u, (n,), 0, vocab))
            return nxt, nxt

        _, rest = jax.lax.scan(step, first,
                               jax.random.split(k_steps, seq_len))
        return jnp.concatenate([first[None], rest], 0).T.astype(jnp.int32)

    toks = walk(k_walk, topic.reshape(-1)).reshape(
        num_clients, n_per_client, seq_len + 1)
    test = walk(k_twalk, t_topic)
    return toks[..., :-1], toks[..., 1:], test[:, :-1], test[:, 1:]


def make_data(seed: int, spec: dict) -> Data:
    """The data of a configuration's ``data`` block, from ``seed``."""
    key = jax.random.fold_in(jax.random.key(seed), 0xDA7A)
    x, y, tx, ty = _make(
        key, int(spec["num_clients"]), int(spec["n_per_client"]),
        int(spec["seq_len"]), int(spec["n_test"]), int(spec["vocab"]),
        int(spec["topics"]), float(spec["follow"]), float(spec["skew"]))
    return Data(x, y, tx, ty, int(spec["vocab"]))


# ---------------------------------------------------------------------------
# Leaves
# ---------------------------------------------------------------------------

def _dims(model: dict) -> dict:
    h = int(model["num_attention_heads"])
    nope, rope = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    return dict(d=int(model["hidden_size"]), h=h, nope=nope, rope=rope,
                qk=nope + rope, v=int(model["v_head_dim"]),
                kv=int(model["kv_lora_rank"]),
                V=int(model["vocab_size"]), L=int(model["num_hidden_layers"]),
                dense=int(model["first_k_dense_replace"]),
                ff=int(model["intermediate_size"]),
                eff=int(model["moe_intermediate_size"]),
                held=int(model["n_routed_experts"]),
                E=int(model["n_routed_experts"])
                * int(model["expert_parallel"]),
                first=int(model["first_expert"]),
                k=int(model["num_experts_per_tok"]),
                shared=int(model["n_shared_experts"]),
                r=int(model["lora_rank"]))


def lora_io(model: dict) -> Dict[str, tuple]:
    """(in, out) of each adaptable projection."""
    m = _dims(model)
    return {"q": (m["d"], m["h"] * m["qk"]),
            "kv_a": (m["d"], m["kv"] + m["rope"]),
            "kv_b": (m["kv"], m["h"] * (m["nope"] + m["v"])),
            "o": (m["h"] * m["v"], m["d"])}


def leaf_shapes(model: dict) -> Dict[str, tuple]:
    """Adapter leaves in the order the program's pytree flattens them:
    layers by index, targets and a/b by name."""
    io, r = lora_io(model), int(model["lora_rank"])
    out = {}
    for i in range(int(model["num_hidden_layers"])):
        for t in sorted(model["lora_targets"]):
            out[f"layers/{i}/{t}/a"] = (io[t][0], r)
            out[f"layers/{i}/{t}/b"] = (r, io[t][1])
    return out


def trunk_shapes(model: dict) -> Dict[str, tuple]:
    """Frozen leaves; a 1-D shape is a norm scale."""
    m = _dims(model)
    d = m["d"]
    out = {"embed": (m["V"], d), "final_norm/scale": (d,),
           "lm_head": (d, m["V"])}
    for i in range(m["L"]):
        p = f"layers/{i}/"
        out.update({p + "attn_norm/scale": (d,), p + "mlp_norm/scale": (d,),
                    p + "attn/wq": (d, m["h"], m["qk"]),
                    p + "attn/w_dkv": (d, m["kv"] + m["rope"]),
                    p + "attn/kv_norm": (m["kv"],),
                    p + "attn/w_uk": (m["kv"], m["h"], m["nope"]),
                    p + "attn/w_uv": (m["kv"], m["h"], m["v"]),
                    p + "attn/wo": (m["h"], m["v"], d)})
        if i < m["dense"]:
            out.update({p + "mlp/wi/w": (d, m["ff"]),
                        p + "mlp/wg/w": (d, m["ff"]),
                        p + "mlp/wo/w": (m["ff"], d)})
        else:
            sff = m["shared"] * m["eff"]
            out.update({p + "moe/router": (d, m["E"]),
                        p + "moe/wi": (m["held"], d, m["eff"]),
                        p + "moe/wg": (m["held"], d, m["eff"]),
                        p + "moe/wo": (m["held"], m["eff"], d),
                        p + "moe/shared/wi/w": (d, sff),
                        p + "moe/shared/wg/w": (d, sff),
                        p + "moe/shared/wo/w": (sff, d)})
    return out


def _key(seed: int, name: str):
    return jax.random.fold_in(jax.random.key(int(seed) + 1),
                              zlib.crc32(name.encode()))


def init_params(seed: int, model: dict):
    """(adapters, trunk), flat ``{name: float32 array}`` dicts."""
    adapters = {}
    for name, shape in leaf_shapes(model).items():
        adapters[name] = jnp.zeros(shape, jnp.float32) \
            if name.endswith("/b") else \
            jax.random.normal(_key(seed, name), shape, jnp.float32) \
            / np.sqrt(shape[0])
    trunk = {}
    for name, shape in trunk_shapes(model).items():
        trunk[name] = jnp.ones(shape, jnp.float32) if len(shape) == 1 \
            else (jax.random.normal(_key(seed, name), shape, jnp.float32)
                  * INIT_STD).astype(jnp.bfloat16).astype(jnp.float32)
    return adapters, trunk


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return w * xf.astype(x.dtype)


def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_cos_sin(model: dict, S: int):
    """HF DeepseekV2YarnRotaryEmbedding: (S, rope) cos and sin."""
    dim = int(model["qk_rope_head_dim"])
    base = float(model["rope_theta"])
    rs = model["rope_scaling"]
    factor = float(rs["factor"])
    orig = float(rs["original_max_position_embeddings"])

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(corr_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / base ** pos
    inter = 1.0 / (factor * base ** pos)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    freqs = np.outer(np.arange(S, dtype=np.float32), inv)
    emb = np.concatenate([freqs, freqs], -1)
    m = yarn_mscale(factor, float(rs["mscale"])) \
        / yarn_mscale(factor, float(rs["mscale_all_dim"]))
    return np.cos(emb) * m, np.sin(emb) * m


def _rope(x, cos, sin):
    """HF apply_rotary_pos_emb: de-interleave, then rotate_half.
    x: (..., S, heads, rope)."""
    *lead, d = x.shape
    x = x.reshape(*lead, d // 2, 2)
    x = jnp.concatenate([x[..., 0], x[..., 1]], -1)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    c = cos[:, None, :].astype(x.dtype)
    s = sin[:, None, :].astype(x.dtype)
    return x * c + rot * s


def _adapt(base, x, ad, name, scale):
    """``base`` plus the adapter ``name``'s (alpha / r) x a b, if held."""
    if name + "/a" not in ad:
        return base
    delta = scale * _mm("...r,ro->...o",
                        _mm("...i,ir->...r", x, ad[name + "/a"]),
                        ad[name + "/b"])
    return base + delta.reshape(base.shape)


def attention(x, p, ad, model, scale):
    """One MLA layer (with its adapters) on x (b, S, d)."""
    m = _dims(model)
    b, S, _ = x.shape
    h, nope, rope, v = m["h"], m["nope"], m["rope"], m["v"]
    q = _adapt(_mm("bsd,dhk->bshk", x, p["attn/wq"]), x, ad, "q", scale)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = _adapt(_mm("bsd,dr->bsr", x, p["attn/w_dkv"]), x, ad, "kv_a",
                 scale)
    c, k_pe = ckv[..., :m["kv"]], ckv[..., m["kv"]:]
    c = _rms(c, p["attn/kv_norm"], float(model["rms_norm_eps"]))
    kvb = _adapt(jnp.concatenate([_mm("bsr,rhk->bshk", c, p["attn/w_uk"]),
                                  _mm("bsr,rhk->bshk", c, p["attn/w_uv"])],
                                 -1), c, ad, "kv_b", scale)
    k_nope, val = kvb[..., :nope], kvb[..., nope:]
    cos, sin = yarn_cos_sin(model, S)
    q_pe = _rope(q_pe, cos, sin)
    k_pe = _rope(k_pe[:, :, None, :], cos, sin)
    qs = jnp.concatenate([q_nope, q_pe], -1)
    ks = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (b, S, h, rope))],
                         -1)
    rs = model["rope_scaling"]
    sm = m["qk"] ** -0.5 * yarn_mscale(float(rs["factor"]),
                                       float(rs["mscale_all_dim"])) ** 2
    outs = []
    for lo in range(0, S, ATTN_BLOCK):
        hi = min(lo + ATTN_BLOCK, S)
        w = _mm("bqhk,bthk->bhqt", qs[:, lo:hi], ks) * sm
        causal = np.arange(S)[None, :] <= np.arange(lo, hi)[:, None]
        w = jnp.where(causal, w.astype(jnp.float32), -jnp.inf)
        w = jax.nn.softmax(w, axis=-1).astype(x.dtype)
        outs.append(_mm("bhqt,bthk->bqhk", w, val))
    o = jnp.concatenate(outs, 1)
    return _adapt(_mm("bshk,hkd->bsd", o, p["attn/wo"]),
                  o.reshape(b, S, h * v), ad, "o", scale)


def _glu(x, wi, wg, wo):
    return _mm("...f,fd->...d", jax.nn.silu(_mm("...d,df->...f", x, wi))
               * _mm("...d,df->...f", x, wg), wo)


def moe(x, p, model):
    """The held experts' part of one MoE layer, plus the shared experts."""
    m = _dims(model)
    f32 = jnp.float32
    logits = _mm("bsd,de->bse", x.astype(f32), p["moe/router"].astype(f32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, m["k"])
    if model["norm_topk_prob"]:
        top_w = top_w / (top_w.sum(-1, keepdims=True) + 1e-20)
    top_w = top_w * float(model["routed_scaling_factor"])
    routed = jnp.zeros(x.shape, f32)
    for e in range(m["held"]):
        gate = jnp.sum(jnp.where(top_i == m["first"] + e, top_w, 0.0), -1)
        routed = routed + gate[..., None] * _glu(
            x, p["moe/wi"][e], p["moe/wg"][e], p["moe/wo"][e]).astype(f32)
    return routed.astype(x.dtype) + _glu(
        x, p["moe/shared/wi/w"], p["moe/shared/wg/w"], p["moe/shared/wo/w"])


def _layer(x, p, ad, model, i, scale):
    eps = float(model["rms_norm_eps"])
    x = x + attention(_rms(x, p["attn_norm/scale"], eps), p, ad, model,
                      scale)
    h = _rms(x, p["mlp_norm/scale"], eps)
    if i < int(model["first_k_dense_replace"]):
        return x + _glu(h, p["mlp/wi/w"], p["mlp/wg/w"], p["mlp/wo/w"])
    return x + moe(h, p, model)


def _sub(tree: dict, prefix: str) -> dict:
    return {k[len(prefix):]: v for k, v in tree.items()
            if k.startswith(prefix)}


def loss_fn(trainable, frozen, x, y, model: dict):
    """Mean next-token cross-entropy of one client's (b, S) batch."""
    scale = float(model["lora_alpha"]) / float(model["lora_rank"])
    h = jnp.take(frozen["embed"], x, axis=0)
    for i in range(int(model["num_hidden_layers"])):
        h = jax.checkpoint(functools.partial(
            _layer, model=model, i=i, scale=scale))(
            h, _sub(frozen, f"layers/{i}/"), _sub(trainable, f"layers/{i}/"))
    h = _rms(h, frozen["final_norm/scale"], float(model["rms_norm_eps"]))
    logits = _mm("bsd,dv->bsv", h, frozen["lm_head"]).astype(jnp.float32)
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(lp, y[..., None], axis=-1).mean()


# ---------------------------------------------------------------------------
# Counts, from shapes alone
# ---------------------------------------------------------------------------

def _token_macs(model: dict, seq_len: int) -> Dict[str, float]:
    """Multiply-accumulates of one token through the trunk and through
    the adapters.  Attention scores and values count the causal half
    ((S + 1) / 2 keys a query); the routed experts count the expected
    ``top_k * held / E`` experts a token (the held share of its picks)."""
    m = _dims(model)
    d, h = m["d"], m["h"]
    proj = d * h * m["qk"] + d * (m["kv"] + m["rope"]) \
        + m["kv"] * h * (m["nope"] + m["v"]) + h * m["v"] * d
    core = (seq_len + 1) / 2 * h * (m["qk"] + m["v"])
    glu = 3 * d
    trunk = m["L"] * (proj + core) + d * m["V"]
    trunk += m["dense"] * glu * m["ff"]
    moe_layers = m["L"] - m["dense"]
    trunk += moe_layers * (d * m["E"] + glu * m["shared"] * m["eff"]
                           + glu * m["eff"] * m["k"] * m["held"] / m["E"])
    io = lora_io(model)
    adapters = m["L"] * sum(m["r"] * (io[t][0] + io[t][1])
                            for t in model["lora_targets"])
    return {"trunk": float(trunk), "adapters": float(adapters)}


def packed_dim(model: dict) -> int:
    """Adapter parameters (the packed row length D)."""
    return int(sum(int(np.prod(s)) for s in leaf_shapes(model).values()))


def train_flops(model: dict, samples: int) -> float:
    """``samples`` sequences of ``seq_len`` tokens, forward and backward:
    2 FLOPs a MAC forward through trunk and adapters, 2 for the input
    gradients back through both, 2 for the adapters' weight gradients;
    the frozen trunk has no weight gradient, and recomputation is not
    counted."""
    macs = _token_macs(model, int(model["seq_len"]))
    tokens = samples * int(model["seq_len"])
    return tokens * (4.0 * (macs["trunk"] + macs["adapters"])
                     + 2.0 * macs["adapters"])


def eval_flops(model: dict, samples: int) -> float:
    macs = _token_macs(model, int(model["seq_len"]))
    return samples * int(model["seq_len"]) * 2.0 * (macs["trunk"]
                                                    + macs["adapters"])

