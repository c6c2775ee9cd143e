"""Pallas kernel allclose sweeps vs pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.kernels.fed_agg.ops import fed_agg
from repro.kernels.fed_agg.ref import fed_agg_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.ssm_scan.ops import ssm_scan

settings.register_profile("kern", max_examples=8, deadline=None)
settings.load_profile("kern")


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (B, Hq, Hkv, S, D, causal, window, dtype)
    (2, 4, 2, 128, 32, True, None, jnp.float32),
    (1, 8, 8, 256, 64, True, 64, jnp.float32),
    (2, 4, 1, 96, 48, True, None, jnp.float32),      # padding path
    (1, 2, 2, 128, 128, False, None, jnp.float32),
    (2, 4, 2, 128, 64, True, None, jnp.bfloat16),
    (1, 6, 3, 64, 64, True, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("B,Hq,Hkv,S,D,causal,window,dtype", FLASH_CASES)
def test_flash_attention_sweep(B, Hq, Hkv, S, D, causal, window, dtype):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, Hq, S, D), dtype)
    k = jnp.asarray(rng.randn(B, Hkv, S, D), dtype)
    v = jnp.asarray(rng.randn(B, Hkv, S, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, impl="pallas_interpret")
    want = attention_ref(q, k, v, causal=causal, window=window)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_matches_model_chunked_attention():
    """Pallas kernel == the model's chunked-XLA path == dense ref."""
    from repro.models.attention import chunked_attention
    rng = np.random.RandomState(1)
    B, S, Hk, G, D = 2, 128, 2, 2, 32
    q = jnp.asarray(rng.randn(B, S, Hk, G, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hk, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hk, D), jnp.float32)
    xla = chunked_attention(q, k, v, causal=True, window=None,
                            scale=D ** -0.5, q_chunk=64, k_chunk=64)
    qc = jnp.transpose(q, (0, 2, 3, 1, 4)).reshape(B, Hk * G, S, D)
    kc = jnp.transpose(k, (0, 2, 1, 3))
    vc = jnp.transpose(v, (0, 2, 1, 3))
    pall = flash_attention(qc, kc, vc, causal=True, block_q=64, block_k=64,
                           impl="pallas_interpret")
    pall = jnp.transpose(pall.reshape(B, Hk, G, S, D), (0, 3, 1, 2, 4))
    np.testing.assert_allclose(np.asarray(xla), np.asarray(pall),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# ssm scan (Mamba2 SSD)
# ---------------------------------------------------------------------------

SSM_CASES = [
    # (B, S, H, P, N, G, chunk, dtype)
    (2, 64, 4, 32, 16, 2, 16, jnp.float32),
    (1, 100, 2, 16, 8, 1, 32, jnp.float32),     # ragged padding
    (2, 128, 4, 64, 64, 4, 64, jnp.float32),
    (1, 64, 2, 32, 16, 2, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,P,N,G,chunk,dtype", SSM_CASES)
def test_ssm_scan_sweep(B, S, H, P, N, G, chunk, dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, H, P), dtype)
    dt = jnp.asarray(rng.rand(B, S, H) * 0.5, jnp.float32)
    A = jnp.asarray(-rng.rand(H) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, G, N), dtype)
    Cm = jnp.asarray(rng.randn(B, S, G, N), dtype)
    y1, h1 = ssm_scan(x, dt, A, Bm, Cm, impl="pallas_interpret",
                      chunk=chunk)
    y2, h2 = ssm_scan(x, dt, A, Bm, Cm, impl="xla")
    tol = 5e-2 if dtype == jnp.bfloat16 else 5e-4
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=tol,
                               atol=tol)


def test_ssm_scan_with_initial_state():
    """Chunked scan continues correctly from a nonzero carried state."""
    rng = np.random.RandomState(2)
    B, S, H, P, N = 1, 64, 2, 16, 8
    x = jnp.asarray(rng.randn(B, S, H, P), jnp.float32)
    dt = jnp.asarray(rng.rand(B, S, H) * 0.3, jnp.float32)
    A = jnp.asarray(-rng.rand(H) - 0.1, jnp.float32)
    Bm = jnp.asarray(rng.randn(B, S, 1, N), jnp.float32)
    Cm = jnp.asarray(rng.randn(B, S, 1, N), jnp.float32)
    # run full sequence vs two halves with carried state
    y_full, h_full = ssm_scan(x, dt, A, Bm, Cm, impl="xla")
    half = S // 2
    y1, h1 = ssm_scan(x[:, :half], dt[:, :half], A, Bm[:, :half],
                      Cm[:, :half], impl="pallas_interpret", chunk=16)
    y2, h2 = ssm_scan(x[:, half:], dt[:, half:], A, Bm[:, half:],
                      Cm[:, half:], h0=h1, impl="pallas_interpret",
                      chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

RWKV_CASES = [
    (2, 2, 48, 16, 16, jnp.float32),
    (1, 4, 100, 32, 32, jnp.float32),
    (2, 2, 64, 64, 64, jnp.float32),
    (1, 2, 32, 32, 16, jnp.bfloat16),
]


@pytest.mark.parametrize("B,H,S,D,chunk,dtype", RWKV_CASES)
def test_rwkv6_scan_sweep(B, H, S, D, chunk, dtype):
    rng = np.random.RandomState(0)
    r = jnp.asarray(rng.randn(B, H, S, D) * 0.5, dtype)
    k = jnp.asarray(rng.randn(B, H, S, D) * 0.5, dtype)
    v = jnp.asarray(rng.randn(B, H, S, D) * 0.5, dtype)
    lw = jnp.asarray(-np.exp(rng.randn(B, H, S, D) * 0.5), jnp.float32)
    u = jnp.asarray(rng.randn(H, D) * 0.3, jnp.float32)
    y1, s1 = rwkv6_scan(r, k, v, lw, u, impl="pallas_interpret",
                        chunk=chunk)
    y2, s2 = rwkv6_scan(r, k, v, lw, u, impl="xla")
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=tol,
                               atol=tol)


def test_rwkv_kernel_plugs_into_model():
    """time_mix(kernel=pallas adapter) == time_mix(exact recurrence)."""
    from repro.configs import get_config
    from repro.kernels.rwkv6_scan.ops import wkv_kernel_adapter
    from repro.models import build_model
    from repro.models import rwkv as R
    import jax
    cfg = get_config("rwkv6-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    p_l = jax.tree.map(lambda a: a[0], params["blocks"])
    x = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    y_exact, s_exact = R.time_mix(p_l["rwkv"], x, cfg, None)
    y_kern, s_kern = R.time_mix(p_l["rwkv"], x, cfg, None,
                                kernel=wkv_kernel_adapter(chunk=16))
    np.testing.assert_allclose(np.asarray(y_exact), np.asarray(y_kern),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_exact), np.asarray(s_kern),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fed_agg
# ---------------------------------------------------------------------------

@given(st.integers(1, 24), st.integers(1, 300),
       st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_fed_agg_property(C, D, dtype):
    rng = np.random.RandomState(C * 1000 + D)
    u = jnp.asarray(rng.randn(C, D), dtype)
    w = jnp.asarray(rng.rand(C), jnp.float32)
    got = fed_agg(u, w, impl="pallas_interpret", block_c=4, block_d=64)
    want = fed_agg_ref(u, w)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_fed_agg_matches_core_aggregation():
    """Pallas fed_agg == repro.core.fed_aggregate on a pytree."""
    from repro import core
    from repro.kernels.fed_agg.ops import fed_agg_tree
    rng = np.random.RandomState(3)
    C = 6
    stacked = {"a": jnp.asarray(rng.randn(C, 4, 5), jnp.float32),
               "b": jnp.asarray(rng.randn(C, 7), jnp.float32)}
    w = jnp.asarray(rng.rand(C), jnp.float32)
    g = {"a": jnp.zeros((4, 5)), "b": jnp.zeros((7,))}
    want = core.fed_aggregate(g, stacked, w)
    got = fed_agg_tree(stacked, w / w.sum(), impl="pallas_interpret")
    for key in ("a", "b"):
        np.testing.assert_allclose(np.asarray(got[key]),
                                   np.asarray(want[key]), rtol=1e-5,
                                   atol=1e-5)


def test_interpret_impl_refused_on_tpu(monkeypatch):
    """The Pallas interpreter is a CPU test tool: on a TPU backend
    ``pallas_interpret`` raises instead of quietly running it."""
    from repro.kernels.fed_agg.ops import fed_agg_packed, interpret_flag
    from repro.kernels.robust_agg.ops import residual_norms
    assert interpret_flag("pallas_interpret") is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_flag("pallas") is False
    u, w = jnp.ones((3, 4)), jnp.ones((3,))
    with pytest.raises(ValueError, match="on a TPU use 'pallas'"):
        fed_agg_packed(u, w, impl="pallas_interpret")
    with pytest.raises(ValueError, match="robust_agg impl 'pallas_interp"):
        residual_norms(u, u[0], impl="pallas_interpret")
