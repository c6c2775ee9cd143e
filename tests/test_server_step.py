"""Packed aggregation + fused server round step: parity vs the leaf-wise
path (fed_aggregate / write_cache / clear_cache sequence the runner used
before the fusion)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs.base import FLConfig
from repro.kernels.fed_agg.ops import fed_agg_packed
from repro.kernels.fed_agg.ref import fed_agg_ref


def _tree(key, C=None, dtypes=(jnp.float32, jnp.float32, jnp.float32)):
    """Ragged-leaf pytree; stacked (C, ...) when C is given."""
    shapes = [(7,), (3, 5), (2, 2, 2)]
    ks = jax.random.split(key, len(shapes))
    lead = () if C is None else (C,)
    return {
        f"l{i}": jax.random.normal(k, lead + s).astype(dt)
        for i, (k, s, dt) in enumerate(zip(ks, shapes, dtypes))
    }


def test_pack_unpack_roundtrip_mixed_dtypes():
    t = _tree(jax.random.key(0),
              dtypes=(jnp.float32, jnp.bfloat16, jnp.float32))
    layout = core.pack_layout(t)
    assert layout.dim == 7 + 15 + 8
    vec = core.pack(t, layout)
    assert vec.shape == (30,) and vec.dtype == jnp.float32
    back = core.unpack(vec, layout)
    for k in t:
        assert back[k].dtype == t[k].dtype
        np.testing.assert_allclose(np.asarray(back[k], np.float32),
                                   np.asarray(t[k], np.float32))


def test_pack_stacked_matches_per_leaf_ravel():
    C = 5
    t = _tree(jax.random.key(1), C=C)
    layout = core.pack_layout(_tree(jax.random.key(1)))
    buf = core.pack_stacked(t, layout)
    assert buf.shape == (C, layout.dim)
    for i, k in enumerate(sorted(t)):
        off, n = layout.offsets[i], layout.sizes[i]
        np.testing.assert_array_equal(np.asarray(buf[:, off:off + n]),
                                      np.asarray(t[k]).reshape(C, -1))


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_matches_leafwise(impl, dtype):
    """Packed whole-model aggregation == leaf-wise fed_aggregate, for
    ragged leaves and C/D not multiples of the kernel block sizes."""
    C = 5                                    # not a multiple of block_c
    g = _tree(jax.random.key(2), dtypes=(dtype,) * 3)
    c = _tree(jax.random.key(3), C=C, dtypes=(dtype,) * 3)
    w = jnp.array([0.5, 0.0, 2.0, 1.0, 0.25])
    want = core.fed_aggregate(g, c, w)
    got = core.fed_aggregate_packed(g, c, w, impl=impl,
                                    block_c=4, block_d=16)
    tol = 1e-6 if dtype == jnp.float32 else 1e-2
    for k in want:
        assert got[k].dtype == want[k].dtype
        np.testing.assert_allclose(np.asarray(got[k], np.float32),
                                   np.asarray(want[k], np.float32),
                                   rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_packed_zero_weights_keeps_global(impl):
    g = _tree(jax.random.key(4))
    c = _tree(jax.random.key(5), C=3)
    out = core.fed_aggregate_packed(g, c, jnp.zeros((3,)), impl=impl)
    for k in g:
        np.testing.assert_array_equal(np.asarray(out[k]), np.asarray(g[k]))


def test_fed_agg_packed_impl_parity():
    C, D = 6, 50                             # both off the block grid
    u = jax.random.normal(jax.random.key(6), (C, D))
    w = jax.random.uniform(jax.random.key(7), (C,))
    want = fed_agg_ref(u, w)
    for impl in ("xla", "pallas_interpret"):
        got = fed_agg_packed(u, w, impl=impl, block_c=4, block_d=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def _old_leafwise_round(global_params, caches, final, cache_p, cached_steps,
                        selected, fail, received, resume, n_samples, rnd,
                        local_steps):
    """The pre-fusion server path, verbatim: host-driven leaf-wise ops."""
    stamp0 = np.asarray(caches.round_stamp)
    base_stale = np.where(resume & (stamp0 >= 0),
                          np.maximum(rnd - stamp0, 0), 0)
    w = core.aggregation_weights(jnp.asarray(received), n_samples=n_samples,
                                 staleness=jnp.asarray(base_stale,
                                                       jnp.float32),
                                 staleness_discount=1.0)
    global_params = core.fed_aggregate(global_params, final, w)
    prior_steps = np.round(np.asarray(caches.progress)
                           * local_steps).astype(np.int32)
    total_cached = np.where(resume, prior_steps, 0) + np.asarray(cached_steps)
    write = selected & fail & (total_cached > 0)
    base_round = np.where(resume & (stamp0 >= 0), stamp0, rnd)
    caches = core.write_cache(
        caches, jnp.asarray(write), cache_p,
        jnp.asarray(total_cached / max(local_steps, 1)).astype(jnp.float32),
        jnp.asarray(base_round, jnp.int32))
    caches = core.clear_cache(caches, jnp.asarray(received))
    return global_params, caches


@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_server_round_step_matches_leafwise_3round_smoke(impl):
    """Acceptance: the fused jitted step reproduces the old leaf-wise
    sequence (weights -> aggregate -> cache write/clear) within 1e-5 over
    a 3-round run with failures, resumes and empty rounds."""
    N, local_steps = 8, 4
    rng = np.random.RandomState(0)
    template = _tree(jax.random.key(8))
    step = core.make_server_round_step(template, local_steps=local_steps,
                                       agg_impl=impl, block_c=4, block_d=16)
    g_new = g_old = template
    caches_new = caches_old = core.init_caches(template, N)
    n_samples = jnp.full((N,), 32.0)
    for rnd in range(3):
        key = jax.random.key(100 + rnd)
        final = _tree(key, C=N)
        cache_p = jax.tree.map(lambda a: a * 0.5, final)
        cached_steps = rng.randint(0, local_steps + 1, N).astype(np.int32)
        selected = rng.rand(N) < 0.8
        fail = selected & (rng.rand(N) < 0.4)
        received = selected & ~fail
        if rnd == 1:
            received[:] = False                    # empty round
        resume = selected & (rng.rand(N) < 0.5)
        g_new, caches_new, _, _, _ = step(
            g_new, caches_new, final, cache_p, jnp.asarray(cached_steps),
            None, jnp.asarray(selected), jnp.asarray(fail),
            jnp.asarray(received), jnp.asarray(resume), n_samples,
            jnp.ones((N,), jnp.float32), rnd)
        g_old, caches_old = _old_leafwise_round(
            g_old, caches_old, final, cache_p, cached_steps, selected,
            fail, received, resume, n_samples, rnd, local_steps)
    for a, b in zip(jax.tree.leaves(g_new), jax.tree.leaves(g_old)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(jax.tree.leaves(caches_new), jax.tree.leaves(caches_old)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_run_fl_agg_impl_parity_smoke():
    """End-to-end: 3 FLUDE rounds with the Pallas interpret kernel match
    the XLA packed path."""
    from repro.data.synthetic import federated_classification
    from repro.fl import SimConfig, run_fl

    data = federated_classification(16, seed=0, n_per_client=32)
    sim = SimConfig(num_clients=16, rounds=3, local_steps=4)
    fl = FLConfig(num_clients=16, clients_per_round=8)
    h_x = run_fl("flude", data, sim, dataclasses.replace(fl,
                                                         agg_impl="xla"))
    h_p = run_fl("flude", data, sim,
                 dataclasses.replace(fl, agg_impl="pallas_interpret"))
    np.testing.assert_allclose(h_x.acc, h_p.acc, atol=1e-5)
    for a, b in zip(jax.tree.leaves(h_x.final_params),
                    jax.tree.leaves(h_p.final_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
