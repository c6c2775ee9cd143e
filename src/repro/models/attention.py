"""Attention substrate: GQA (+ sliding window, qkv bias), MLA, KV caches.

Layouts
-------
q:      (B, S, Hkv, G, D)   — G = query-group size = Hq // Hkv
k, v:   (B, S, Hkv, D)
cache:  KVCache with k/v of (B, S_max, Hkv, D) (ring-buffered for SWA)

The train/prefill path is a chunked online-softmax (flash-style) written in
pure lax.scan so that the dry-run never materializes (S, S) score tensors.
The Pallas TPU kernel (repro.kernels.flash_attention) implements the same
contract for the real-hardware path; tests cross-check all implementations.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array          # (B, S_cache, Hkv, D)
    v: jax.Array          # (B, S_cache, Hkv, D)
    length: jax.Array     # (B,) valid prefix length (== insert position)


class MLACache(NamedTuple):
    c_kv: jax.Array       # (B, S_cache, kv_lora)
    k_rope: jax.Array     # (B, S_cache, rope_dim)
    length: jax.Array     # (B,)


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------

def gqa_spec(cfg, layered: Optional[int] = None):
    d, hk = cfg.d_model, cfg.num_kv_heads
    g = cfg.num_heads // hk
    hd = cfg.resolved_head_dim
    dt = L.cfg_dtype(cfg.param_dtype)

    def w(shape, axes, init="normal", scale=1.0, fan_in=None):
        if layered is not None:
            shape = (layered,) + shape
            axes = ("layers",) + axes
        return L.ParamSpec(shape, dt, axes, init, scale, fan_in=fan_in)

    # explicit fan_in: the shape heuristic reads dim -2, which for these
    # multi-dim projections is a head axis, not the contraction size —
    # mis-scaled init saturates the score softmax
    p = {
        "wq": w((d, hk, g, hd), ("embed", "kv_heads", "q_group", "head_dim"),
                fan_in=d),
        "wk": w((d, hk, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wv": w((d, hk, hd), ("embed", "kv_heads", "head_dim"), fan_in=d),
        "wo": w((hk, g, hd, d), ("kv_heads", "q_group", "head_dim", "embed"),
                fan_in=hk * g * hd),
    }
    if cfg.qkv_bias:
        p["bq"] = w((hk, g, hd), ("kv_heads", "q_group", "head_dim"), "zeros")
        p["bk"] = w((hk, hd), ("kv_heads", "head_dim"), "zeros")
        p["bv"] = w((hk, hd), ("kv_heads", "head_dim"), "zeros")
    return p


def mla_spec(cfg, layered: Optional[int] = None):
    m = cfg.mla
    d, h = cfg.d_model, cfg.num_heads
    dt = L.cfg_dtype(cfg.param_dtype)

    def w(shape, axes, init="normal", fan_in=None):
        if layered is not None:
            shape = (layered,) + shape
            axes = ("layers",) + axes
        return L.ParamSpec(shape, dt, axes, init, 1.0, fan_in=fan_in)

    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank is None:
        # one plain query projection (q_proj)
        q = {"wq": w((d, h, qk_dim), ("embed", "heads", "head_dim"),
                     fan_in=d)}
    else:
        # query low-rank path
        q = {"w_dq": w((d, m.q_lora_rank), ("embed", "lora")),
             "q_norm": w((m.q_lora_rank,), ("lora",), "ones"),
             "w_uq": w((m.q_lora_rank, h, qk_dim),
                       ("lora", "heads", "head_dim"),
                       fan_in=m.q_lora_rank)}
    return {
        **q,
        # kv low-rank path (+ shared rope key)
        "w_dkv": w((d, m.kv_lora_rank + m.qk_rope_head_dim),
                   ("embed", "lora")),
        "kv_norm": w((m.kv_lora_rank,), ("lora",), "ones"),
        "w_uk": w((m.kv_lora_rank, h, m.qk_nope_head_dim),
                  ("lora", "heads", "head_dim"), fan_in=m.kv_lora_rank),
        "w_uv": w((m.kv_lora_rank, h, m.v_head_dim),
                  ("lora", "heads", "head_dim"), fan_in=m.kv_lora_rank),
        "wo": w((h, m.v_head_dim, d), ("heads", "head_dim", "embed"),
                fan_in=h * m.v_head_dim),
    }


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (train / prefill), pure XLA
# ---------------------------------------------------------------------------

def _block(q, k, v, bias):
    """q: (B,Bq,Hk,G,D) k/v: (B,Bk,Hk,D) bias: (Bq,Bk) -> partial softmax."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s + bias[None, None, None]
    m = s.max(-1)                                           # (B,Hk,G,Bq)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def chunked_attention(q, k, v, *, causal: bool, window: Optional[int],
                      scale: float, q_offset=0,
                      q_chunk: int = 512, k_chunk: int = 512,
                      unroll_causal: bool = False, ecfg=None):
    """Flash-style attention via nested scans.

    q: (B, Sq, Hk, G, D); k, v: (B, Sk, Hk, D).  ``q_offset`` is the absolute
    position of q[0] relative to k[0] (for prefill-continuation).  Returns
    (B, Sq, Hk, G, D).
    """
    B, Sq, Hk, G, D = q.shape
    Dv = v.shape[-1]
    Sk = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    assert Sq % q_chunk == 0 and Sk % k_chunk == 0
    nq, nk = Sq // q_chunk, Sk // k_chunk
    q = (q * scale).reshape(B, nq, q_chunk, Hk, G, D)

    q_pos = jnp.arange(q_chunk)
    k_pos = jnp.arange(k_chunk)

    def kv_bias(iq, jk):
        """(Bq, Bk) additive mask bias for q block iq vs kv block jk."""
        qp = q_offset + iq * q_chunk + q_pos[:, None]
        kp = jk * k_chunk + k_pos[None, :]
        ok = jnp.ones((q_chunk, k_chunk), bool)
        if causal:
            ok &= kp <= qp
        if window is not None:
            ok &= kp > qp - window
        return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)

    def one_q_block(qi, iq, jks, valids=None):
        """Online softmax over the given kv block indices."""
        m0 = jnp.full((B, Hk, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hk, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, Hk, G, q_chunk, Dv), jnp.float32)
        if valids is None:
            valids = jnp.ones(jks.shape, bool)

        def body(carry, jk_valid):
            jk, valid = jk_valid
            m, l, o = carry
            kb = jax.lax.dynamic_slice_in_dim(k, jk * k_chunk, k_chunk, 1)
            vb = jax.lax.dynamic_slice_in_dim(v, jk * k_chunk, k_chunk, 1)
            # pin the block layout INSIDE the loop: scan-carry shardings
            # are otherwise XLA's choice, and it picked head_dim-contraction
            # sharding (a per-block score psum — §Perf llama3 iteration 2)
            kb = L.shard_act(kb, ("batch", None, "kv_heads", "head_dim"),
                             ecfg)
            vb = L.shard_act(vb, ("batch", None, "kv_heads", "head_dim"),
                             ecfg)
            qb = L.shard_act(qi, ("batch", None, "kv_heads", "q_group",
                                  "head_dim"), ecfg)
            bias = kv_bias(iq, jk) + jnp.where(valid, 0.0, NEG_INF)
            mb, lb, ob = _block(qb, kb, vb, bias)
            m_new = jnp.maximum(m, mb)
            a1 = jnp.exp(m - m_new)
            a2 = jnp.exp(mb - m_new)
            return (m_new, l * a1 + lb * a2,
                    o * a1[..., None] + ob * a2[..., None]), None

        # remat the block: otherwise backward saves per-iteration (Bq, Bk)
        # score tensors for every kv block (O(S²) residuals)
        (m, l, o), _ = jax.lax.scan(jax.remat(body), (m0, l0, o0),
                                    (jks, valids))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        # (B,Hk,G,Bq,D) -> (B,Bq,Hk,G,D)
        return jnp.transpose(o, (0, 3, 1, 2, 4)).astype(v.dtype)

    if window is not None and causal:
        # banded: a q block [qlo, qhi] needs kv positions
        # (qlo - window, qhi] — at most ceil((q_chunk + window)/k_chunk)+1
        # kv blocks.  Out-of-range block indices are masked (NOT clamped:
        # clamping would double-visit block 0 and skew the softmax).
        wblocks = min(-(-(q_chunk + window) // k_chunk) + 1, nk)

        def per_q(carry, iq):
            qi = jax.lax.dynamic_index_in_dim(q, iq, 1, keepdims=False)
            last = (q_offset + (iq + 1) * q_chunk - 1) // k_chunk
            raw = last - jnp.arange(wblocks)[::-1]
            valids = (raw >= 0) & (raw <= nk - 1)
            jks = jnp.clip(raw, 0, nk - 1)
            return carry, one_q_block(qi, iq, jks, valids)

        _, out = jax.lax.scan(per_q, None, jnp.arange(nq))
    elif causal and unroll_causal:
        # unrolled causal pruning: q block i only visits kv blocks <= i
        outs = []
        for i in range(nq):
            last = (q_offset + (i + 1) * q_chunk - 1) // k_chunk
            outs.append(one_q_block(q[:, i], i, jnp.arange(last + 1)))
        out = jnp.stack(outs, 0)
    else:
        def per_q(carry, iq):
            qi = jax.lax.dynamic_index_in_dim(q, iq, 1, keepdims=False)
            return carry, one_q_block(qi, iq, jnp.arange(nk))

        _, out = jax.lax.scan(per_q, None, jnp.arange(nq))

    # out: (nq, B, Bq, Hk, G, Dv) -> (B, Sq, Hk, G, Dv)
    return jnp.transpose(out, (1, 0, 2, 3, 4, 5)).reshape(B, Sq, Hk, G, Dv)


def dense_attention(q, k, v, *, causal, window, scale, q_offset=0):
    """Naive dense reference (tests / tiny shapes only)."""
    B, Sq, Hk, G, D = q.shape
    Sk = k.shape[1]
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    qp = q_offset + jnp.arange(Sq)[:, None]
    kp = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    s = jnp.where(ok[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v)
    return jnp.transpose(o, (0, 3, 1, 2, 4))


# ---------------------------------------------------------------------------
# GQA block forward
# ---------------------------------------------------------------------------

def _project_qkv(p, x, cfg):
    dt = x.dtype
    q = jnp.einsum("bsd,dhgk->bshgk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def gqa_forward(p, x, positions, cfg, *, causal: bool = True,
                q_chunk: int = 512, k_chunk: int = 512,
                unroll_causal: bool = False, impl: str = "chunked",
                ecfg=None):
    """Full-sequence attention (train / encoder / prefill).

    x: (B, S, d); positions: (B, S) absolute positions.
    """
    q, k, v = _project_qkv(p, x, cfg)
    q = L.shard_act(q, ("batch", None, "kv_heads", "q_group", "head_dim"),
                    ecfg)
    k = L.shard_act(k, ("batch", None, "kv_heads", "head_dim"), ecfg)
    v = L.shard_act(v, ("batch", None, "kv_heads", "head_dim"), ecfg)
    q = L.apply_rope(q.reshape(q.shape[:2] + (-1, q.shape[-1])),
                     positions, cfg.rope_theta).reshape(q.shape)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    scale = cfg.resolved_head_dim ** -0.5
    if impl == "dense":
        o = dense_attention(q, k, v, causal=causal,
                            window=cfg.sliding_window, scale=scale)
    else:
        o = chunked_attention(q, k, v, causal=causal,
                              window=cfg.sliding_window, scale=scale,
                              q_chunk=q_chunk, k_chunk=k_chunk,
                              unroll_causal=unroll_causal, ecfg=ecfg)
    return jnp.einsum("bshgk,hgkd->bsd", o, p["wo"].astype(x.dtype))


def gqa_prefill(p, x, positions, cfg, cache: KVCache, ecfg=None, **kw):
    """Prefill: run full attention AND fill the cache.

    k/v are pinned to the attention-core sharding (replicated over model
    for GQA archs whose kv_heads don't divide the model axis) so the
    decode cache's head_dim sharding cannot propagate INTO the attention
    contraction — that propagation forced a per-block score psum measured
    at 5.2e3 s of wire time on llama3 prefill_32k (§Perf llama3 it.1).
    The reshard happens once at the cache write instead.
    """
    q, k, v = _project_qkv(p, x, cfg)
    q = L.shard_act(q, ("batch", None, "kv_heads", "q_group", "head_dim"),
                    ecfg)
    k = L.shard_act(k, ("batch", None, "kv_heads", "head_dim"), ecfg)
    v = L.shard_act(v, ("batch", None, "kv_heads", "head_dim"), ecfg)
    q = L.apply_rope(q.reshape(q.shape[:2] + (-1, q.shape[-1])),
                     positions, cfg.rope_theta).reshape(q.shape)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    scale = cfg.resolved_head_dim ** -0.5
    o = chunked_attention(q, k, v, causal=True, window=cfg.sliding_window,
                          scale=scale, ecfg=ecfg, **kw)
    out = jnp.einsum("bshgk,hgkd->bsd", o, p["wo"].astype(x.dtype))
    S = x.shape[1]
    Sc = cache.k.shape[1]
    if Sc >= S:
        newk = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), 0, 1)
        newv = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), 0, 1)
    else:   # ring cache smaller than prompt (SWA): keep the tail, placed
        # at ring index p mod Sc (decode's slotting discipline)
        newk = jnp.roll(k[:, S - Sc:], S % Sc, axis=1).astype(cache.k.dtype)
        newv = jnp.roll(v[:, S - Sc:], S % Sc, axis=1).astype(cache.v.dtype)
    return out, KVCache(newk, newv, jnp.full_like(cache.length, S))


def gqa_decode_step(p, x, positions, cfg, cache: KVCache):
    """One-token decode: x (B, 1, d), positions (B, 1) absolute.

    The cache is a ring buffer of size S_cache; for SWA archs S_cache ==
    sliding_window so the 500k-context decode stays O(window).
    """
    q, k, v = _project_qkv(p, x, cfg)
    q = L.apply_rope(q.reshape(q.shape[:2] + (-1, q.shape[-1])),
                     positions, cfg.rope_theta).reshape(q.shape)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    B, _, Hk, D = k.shape
    Sc = cache.k.shape[1]
    slot = (cache.length % Sc)[:, None, None, None]          # (B,1,1,1)
    oh = (jnp.arange(Sc)[None, :, None, None] == slot)
    newk = jnp.where(oh, k.astype(cache.k.dtype), cache.k)
    newv = jnp.where(oh, v.astype(cache.v.dtype), cache.v)

    # positions of cache slots (ring-aware), for masking + rope already baked
    slot_idx = jnp.arange(Sc)[None, :]                       # (1, Sc)
    n_written = jnp.minimum(cache.length[:, None] + 1, Sc)   # (B,1)
    # valid if the slot has been written
    wrapped = (cache.length[:, None] + 1) > Sc
    valid = jnp.where(wrapped, jnp.ones((B, Sc), bool),
                      slot_idx < n_written)

    s = jnp.einsum("bqhgd,bkhd->bhgqk", q * (cfg.resolved_head_dim ** -0.5),
                   newk.astype(q.dtype), preferred_element_type=jnp.float32)
    s = jnp.where(valid[:, None, None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bhgqd", prob.astype(newv.dtype),
                   newv.astype(q.dtype))
    o = jnp.transpose(o, (0, 3, 1, 2, 4))
    out = jnp.einsum("bshgk,hgkd->bsd", o, p["wo"].astype(x.dtype))
    return out, KVCache(newk, newv, cache.length + 1)


def init_kv_cache(cfg, batch: int, max_len: int, filled: bool = False):
    Sc = max_len if cfg.sliding_window is None else min(
        max_len, cfg.sliding_window)
    dt = L.cfg_dtype(cfg.param_dtype)
    hd = cfg.resolved_head_dim
    shape = (batch, Sc, cfg.num_kv_heads, hd)
    length = jnp.full((batch,), max_len if filled else 0, jnp.int32)
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt), length)


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2 Multi-head Latent Attention)
# ---------------------------------------------------------------------------

def _adapt(adapter, name, inp, base):
    """``base`` plus the adapter's delta for projection ``name`` (added
    in float32, returned in ``base``'s dtype); ``base`` alone without an
    adapter.  ``adapter(name, inp)`` maps the projection's input to a
    (..., out) delta, ``out`` the projection's flattened output width."""
    if adapter is None:
        return base
    d = adapter(name, inp)
    return (base.astype(jnp.float32) + d.reshape(base.shape)) \
        .astype(base.dtype)


def _mla_qkv(p, x, positions, cfg, adapter=None):
    m = cfg.mla
    dt = x.dtype
    if "wq" in p:
        q = _adapt(adapter, "q", x,
                   jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt)))
    else:
        cq = jnp.einsum("bsd,dr->bsr", x, p["w_dq"].astype(dt))
        cq = _rms(cq, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"].astype(dt))
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = L.apply_rope(_deinterleave(q_rope), positions, cfg.rope_theta,
                          cfg.rope_scaling)

    ckv_full = _adapt(adapter, "kv_a", x,
                      jnp.einsum("bsd,dr->bsr", x, p["w_dkv"].astype(dt)))
    c_kv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = _rms(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = L.apply_rope(_deinterleave(k_rope[:, :, None, :]), positions,
                          cfg.rope_theta, cfg.rope_scaling)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def _deinterleave(x):
    """DeepSeek-V2's rope layout: the rope dims hold (2i, 2i+1) pairs,
    regrouped into halves before the half-rotation (HF
    ``apply_rotary_pos_emb``)."""
    *lead, d = x.shape
    return jnp.swapaxes(x.reshape(*lead, d // 2, 2), -1, -2) \
        .reshape(*lead, d)


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt((xf ** 2).mean(-1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def mla_softmax_scale(cfg) -> float:
    """1/sqrt(qk head dim), times mscale(mscale_all_dim)^2 under YaRN."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_forward(p, x, positions, cfg, *, q_chunk=512, k_chunk=512,
                unroll_causal=False, impl="chunked", adapter=None):
    """MLA attention via decompression into per-head K/V (train/prefill).

    ``adapter`` (optional, see ``_adapt``) adds low-rank deltas to the
    ``q``, ``kv_a`` (the joint latent + rope-key projection), ``kv_b``
    (the K and V decompressions, one (h * (nope + v)) output split per
    head) and ``o`` projections."""
    m = cfg.mla
    with jax.named_scope("mla"):
        q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, positions, cfg,
                                                adapter)
        dt = x.dtype
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"].astype(dt))
        val = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uv"].astype(dt))
        if adapter is not None:
            kv = _adapt(adapter, "kv_b", c_kv,
                        jnp.concatenate([k_nope, val], -1))
            k_nope, val = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
        B, S, H, _ = k_nope.shape
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, m.qk_rope_head_dim))], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
        # treat as MHA: Hk = H, G = 1; the attention core takes a v width
        # of its own
        scale = mla_softmax_scale(cfg)
        q5 = q[:, :, :, None, :]                          # (B,S,H,1,Dqk)
        if impl == "dense":
            o = dense_attention(q5, k, val, causal=True, window=None,
                                scale=scale)
        else:
            o = chunked_attention(q5, k, val, causal=True, window=None,
                                  scale=scale, q_chunk=q_chunk,
                                  k_chunk=k_chunk,
                                  unroll_causal=unroll_causal)
        o = o[:, :, :, 0, :]                              # (B,S,H,Dv)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
        return _adapt(adapter, "o", o.reshape(B, S, -1), out)


def mla_prefill(p, x, positions, cfg, cache: MLACache, **kw):
    out = mla_forward(p, x, positions, cfg, **kw)
    _, _, c_kv, k_rope = _mla_qkv(p, x, positions, cfg)
    S = x.shape[1]
    new = MLACache(
        jax.lax.dynamic_update_slice_in_dim(
            cache.c_kv, c_kv.astype(cache.c_kv.dtype), 0, 1),
        jax.lax.dynamic_update_slice_in_dim(
            cache.k_rope, k_rope.astype(cache.k_rope.dtype), 0, 1),
        jnp.full_like(cache.length, S))
    return out, new


def mla_decode_step(p, x, positions, cfg, cache: MLACache):
    """One-token decode against the *compressed* cache (MLA's raison d'être).

    Scores are computed in latent space: q_nope is absorbed through w_uk so
    the per-token cache stays (kv_lora + rope_dim) wide.
    """
    dt = x.dtype
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkv(p, x, positions, cfg)

    B = x.shape[0]
    Sc = cache.c_kv.shape[1]
    slot = (cache.length % Sc)[:, None, None]
    oh = (jnp.arange(Sc)[None, :, None] == slot)
    c_kv = jnp.where(oh, c_kv_new.astype(cache.c_kv.dtype), cache.c_kv)
    k_rope = jnp.where(oh, k_rope_new.astype(cache.k_rope.dtype),
                       cache.k_rope)

    # absorb: q_lat[b,h,r] = sum_k q_nope[b,h,k] * w_uk[r,h,k]
    q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"].astype(dt))
    s_nope = jnp.einsum("bshr,btr->bhst", q_lat, c_kv.astype(dt),
                        preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bshk,btk->bhst", q_rope, k_rope.astype(dt),
                        preferred_element_type=jnp.float32)
    s = (s_nope + s_rope) * mla_softmax_scale(cfg)
    valid = jnp.arange(Sc)[None, :] < jnp.minimum(
        cache.length[:, None] + 1, Sc)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    prob = jax.nn.softmax(s, axis=-1).astype(dt)
    # o_lat[b,h,r] then decompress through w_uv
    o_lat = jnp.einsum("bhst,btr->bshr", prob, c_kv.astype(dt))
    o = jnp.einsum("bshr,rhk->bshk", o_lat, p["w_uv"].astype(dt))
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(dt))
    return out, MLACache(c_kv, k_rope, cache.length + 1)


def init_mla_cache(cfg, batch: int, max_len: int, filled: bool = False):
    m = cfg.mla
    dt = L.cfg_dtype(cfg.param_dtype)
    length = jnp.full((batch,), max_len if filled else 0, jnp.int32)
    return MLACache(
        jnp.zeros((batch, max_len, m.kv_lora_rank), dt),
        jnp.zeros((batch, max_len, m.qk_rope_head_dim), dt),
        length)
