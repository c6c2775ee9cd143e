"""Server round-step benchmark: leaf-wise vs packed aggregation, and the
old host-driven round tail vs the fused jitted ``server_round_step``.

Two comparisons across fleet size C (paper §4.3 hot spot):

  * ``agg``: per-leaf ``fed_aggregate`` (one XLA op chain per leaf) vs the
    packed single-buffer path (one aggregation over the whole model).
  * ``round_tail``: the pre-fusion sequence (host staleness math + leaf-wise
    aggregate + cache write/clear, each a separate dispatch) vs one
    ``server_round_step`` call.

CPU timings measure dispatch/fusion overhead, not TPU kernel speed — the
Pallas path is exercised for parity in tests and on TPU via
``FLConfig.agg_impl="pallas"``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, emit
from repro import core
from repro.fl import classifier as CLF

FLEETS = (32, 256) if QUICK else (32, 256, 1024)
LOCAL_STEPS = 4


def _time(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps * 1e6


def _fleet(C, rng):
    g = CLF.init_classifier(jax.random.key(0), dim=32)
    final = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(C, *a.shape), a.dtype), g)
    w = jnp.asarray(rng.rand(C), jnp.float32)
    return g, final, w


def _old_round_tail(g, caches, final, w_inputs, local_steps):
    """Pre-fusion server tail, verbatim host-driven sequence."""
    selected, fail, received, resume, n_samples, rnd = w_inputs
    stamp0 = np.asarray(caches.round_stamp)
    base_stale = np.where(resume & (stamp0 >= 0),
                          np.maximum(rnd - stamp0, 0), 0)
    w = core.aggregation_weights(jnp.asarray(received), n_samples=n_samples,
                                 staleness=jnp.asarray(base_stale,
                                                       jnp.float32),
                                 staleness_discount=1.0)
    g = core.fed_aggregate(g, final, w)
    prior = np.round(np.asarray(caches.progress)
                     * local_steps).astype(np.int32)
    total = np.where(resume, prior, 0) + local_steps
    write = selected & fail & (total > 0)
    base_round = np.where(resume & (stamp0 >= 0), stamp0, rnd)
    caches = core.write_cache(
        caches, jnp.asarray(write), final,
        jnp.asarray(total / max(local_steps, 1)).astype(jnp.float32),
        jnp.asarray(base_round, jnp.int32))
    caches = core.clear_cache(caches, jnp.asarray(received))
    return g, caches


def run():
    rng = np.random.RandomState(0)
    for C in FLEETS:
        g, final, w = _fleet(C, rng)
        D = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(g))

        # -- aggregation only: leaf-wise tree.map vs packed single buffer
        leafwise = jax.jit(lambda gp, cp, ww: core.fed_aggregate(gp, cp, ww))
        packed = jax.jit(lambda gp, cp, ww: core.fed_aggregate_packed(
            gp, cp, ww, impl="xla"))
        us_leaf = _time(leafwise, g, final, w)
        us_pack = _time(packed, g, final, w)
        emit(f"server_agg_leafwise_C{C}", us_leaf, f"D={D}")
        emit(f"server_agg_packed_C{C}", us_pack,
             f"D={D};speedup={us_leaf / max(us_pack, 1e-9):.2f}x")

        # -- full round tail: old host-driven sequence vs fused jitted step
        caches = core.init_caches(g, C)
        selected = rng.rand(C) < 0.8
        fail = selected & (rng.rand(C) < 0.3)
        received = selected & ~fail
        resume = selected & (rng.rand(C) < 0.5)
        n_samples = jnp.full((C,), 48.0)
        step = core.make_server_round_step(g, local_steps=LOCAL_STEPS,
                                           agg_impl="xla")
        cached_steps = jnp.full((C,), LOCAL_STEPS, jnp.int32)
        args = (g, caches, final, final, cached_steps, None,
                jnp.asarray(selected), jnp.asarray(fail),
                jnp.asarray(received), jnp.asarray(resume), n_samples,
                jnp.ones((C,), jnp.float32), 3)
        us_fused = _time(lambda *a: step(*a), *args)
        w_inputs = (selected, fail, received, resume, n_samples, 3)
        us_old = _time(
            lambda: _old_round_tail(g, caches, final, w_inputs, LOCAL_STEPS))
        emit(f"server_round_old_C{C}", us_old, f"D={D}")
        emit(f"server_round_fused_C{C}", us_fused,
             f"D={D};speedup={us_old / max(us_fused, 1e-9):.2f}x")


if __name__ == "__main__":
    run()
