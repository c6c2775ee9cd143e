"""Fleet-mesh round path: sharded-vs-single-device parity + donation.

Multi-device tests fork a python with 8 forced host devices (via
``repro.launch.mesh.force_host_platform_device_count`` — applied before
any jax import) and compare against the single-device path *inside* the
subprocess, so the main pytest process keeps its 1 device.

Donation needs no subprocess: CPU jax invalidates donated buffers, so the
tests assert the dead round inputs really are deleted after the call.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs.base import FLConfig
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, SimConfig
from repro.fl.engine import make_trainer


def _run(script, timeout=540):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# Packed aggregation: shard_map partial sums + psum vs the flat kernel
# ---------------------------------------------------------------------------

_AGG_SCRIPT = r"""
from repro.launch.mesh import force_host_platform_device_count
force_host_platform_device_count(8)
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.fed_agg.ops import fed_agg_packed, fed_agg_packed_sharded
from repro.launch.mesh import make_fleet_mesh
from repro.sharding.partitioning import fleet_sharding

C, D = 32, 3000
rng = np.random.RandomState(0)
u = jnp.asarray(rng.randn(C, D).astype(np.float32))
w = jnp.asarray(rng.rand(C).astype(np.float32))
w = w / w.sum()

ref = fed_agg_packed(u, w, impl="xla")

mesh = make_fleet_mesh(8)
u_sh = jax.device_put(u, fleet_sharding(mesh, 2))
w_sh = jax.device_put(w, fleet_sharding(mesh, 1))
errs = {}
for impl in ("xla", "pallas_interpret"):
    out = jax.jit(lambda a, b: fed_agg_packed_sharded(
        a, b, mesh=mesh, impl=impl, block_c=8, block_d=512))(u_sh, w_sh)
    errs[impl] = float(jnp.abs(out - ref).max() /
                       jnp.abs(ref).max())
print(json.dumps({"n_dev": len(jax.devices()), **errs}))
"""


@pytest.mark.slow
def test_packed_aggregation_sharded_matches_single_device():
    """Per-shard partial weighted sums + fp32 psum agree with the flat
    single-device packed kernel for both the xla and the (interpreted)
    pallas per-shard impls."""
    rec = _run(_AGG_SCRIPT)
    assert rec["n_dev"] == 8
    assert rec["xla"] < 1e-5
    assert rec["pallas_interpret"] < 1e-5


# ---------------------------------------------------------------------------
# Engine: 3-round sharded run reproduces the single-device trajectory
# ---------------------------------------------------------------------------

_ENGINE_SCRIPT = r"""
from repro.launch.mesh import force_host_platform_device_count
force_host_platform_device_count(8)
import dataclasses
import json
import numpy as np

from repro.configs.base import FLConfig
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, SimConfig, available_policies

n = 32
data = federated_classification(n, seed=0, n_per_client=32)
sim = SimConfig(num_clients=n, rounds=3, seed=0, local_steps=2)
fl = FLConfig(num_clients=n, clients_per_round=8)

out = {"n_dev": 0, "policies": {}}
import jax
out["n_dev"] = len(jax.devices())
for policy in sorted(available_policies()):
    ref = FleetEngine(data, sim, fl).run(policy, diagnostics=False)
    fl_m = dataclasses.replace(fl, mesh_shape=(8,), donate_buffers=True)
    h = FleetEngine(data, sim, fl_m).run(policy, diagnostics=False)
    out["policies"][policy] = {
        "acc_exact": h.acc == ref.acc,
        "acc_err": float(max(abs(a - b) for a, b in zip(h.acc, ref.acc))),
        "ints_exact": (h.received == ref.received
                       and h.selected == ref.selected),
        "wall_exact": h.wall_clock == ref.wall_clock,
        "comm_exact": h.comm_mb == ref.comm_mb,
    }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_engine_matches_single_device_3rounds():
    """Every registered policy's 3-round History on the forced-8-device
    client mesh (with donation) reproduces the single-device trajectory.

    The host-side trajectory (selected/received/wall clock/comm) must be
    exact.  Accuracy is asserted to 1e-6: the sharded psum uses a
    different fp32 reduction order than the flat einsum, so bit-equality
    of the model — observed on the pinned CI toolchain, where acc comes
    out exactly equal too — is not guaranteed across CPU microarchs.
    """
    rec = _run(_ENGINE_SCRIPT, timeout=540)
    assert rec["n_dev"] == 8
    for policy, r in rec["policies"].items():
        assert r["ints_exact"], (policy, r)
        assert r["wall_exact"] and r["comm_exact"], (policy, r)
        assert r["acc_err"] < 1e-6, (policy, r)


_SHARDED_STATE_SCRIPT = r"""
from repro.launch.mesh import force_host_platform_device_count
force_host_platform_device_count(8)
import dataclasses
import json
import jax

from repro.configs.base import FLConfig
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, SimConfig

n = 32
data = federated_classification(n, seed=0, n_per_client=32)
sim = SimConfig(num_clients=n, rounds=2, seed=0, local_steps=2)
fl = FLConfig(num_clients=n, clients_per_round=8, mesh_shape=(8,))
engine = FleetEngine(data, sim, fl)
h = engine.run("flude", diagnostics=False)
caches = engine._last_caches
leaf = jax.tree.leaves(caches.params)[0]
print(json.dumps({
    "n_dev": len(jax.devices()),
    "cache_shards": len(leaf.sharding.device_set),
    "scalar_shards": len(caches.progress.sharding.device_set),
    "global_replicated": all(
        len(l.sharding.device_set) == 8 and
        l.sharding.is_fully_replicated
        for l in jax.tree.leaves(h.final_params)),
}))
"""


@pytest.mark.slow
def test_fleet_state_stays_sharded_across_rounds():
    """After a run, the caches (stacked pytree + per-client scalars) still
    live sharded over all 8 devices and the global model is replicated —
    rounds never silently collapse the fleet onto one device."""
    rec = _run(_SHARDED_STATE_SCRIPT)
    assert rec["n_dev"] == 8
    assert rec["cache_shards"] == 8
    assert rec["scalar_shards"] == 8
    assert rec["global_replicated"]


# ---------------------------------------------------------------------------
# Donation: dead round inputs are actually invalidated (and values agree)
# ---------------------------------------------------------------------------

def _toy_round_inputs(n=8):
    template = {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": jnp.ones((3,), jnp.float32)}
    caches = core.init_caches(template, n)
    rng = np.random.RandomState(0)
    final = jax.tree.map(
        lambda a: jnp.asarray(rng.randn(n, *a.shape).astype(np.float32)),
        template)
    cache_p = jax.tree.map(jnp.zeros_like, final)
    cached_steps = jnp.zeros((n,), jnp.int32)
    sel = jnp.asarray(rng.rand(n) < 0.7)
    fail = jnp.zeros((n,), bool)
    received = sel
    resume = jnp.zeros((n,), bool)
    n_samples = jnp.full((n,), 4.0, jnp.float32)
    ones = jnp.ones((n,), jnp.float32)
    return (template, caches, final, cache_p, cached_steps, sel, fail,
            received, resume, n_samples, ones)


def test_server_step_donation_invalidates_inputs():
    (template, caches, final, cache_p, cached_steps, sel, fail, received,
     resume, n_samples, ones) = _toy_round_inputs()
    ref_step = core.make_server_round_step(template, local_steps=2,
                                           donate=False)
    ref_g, ref_c, _, _, _ = ref_step(template, caches, final, cache_p,
                                     cached_steps, None, sel, fail,
                                     received, resume, n_samples, ones, 0)

    (template2, caches2, final2, cache_p2, cached_steps2, *_) = \
        _toy_round_inputs()
    don_step = core.make_server_round_step(template2, local_steps=2,
                                           donate=True)
    g_in = jax.tree.map(jnp.copy, template2)
    got_g, got_c, _, _, _ = don_step(g_in, caches2, final2, cache_p2,
                                     cached_steps2, None, sel, fail,
                                     received, resume, n_samples, ones, 0)
    # donated inputs (previous global model + caches) are dead...
    assert all(l.is_deleted() for l in jax.tree.leaves(g_in))
    assert all(l.is_deleted() for l in jax.tree.leaves(caches2))
    # ...the undonated stacked trainer outputs are not...
    assert not any(l.is_deleted() for l in jax.tree.leaves(final2))
    assert not any(l.is_deleted() for l in jax.tree.leaves(cache_p2))
    # ...and donation changes no values
    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(got_g)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(ref_c), jax.tree.leaves(got_c)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_trainer_donation_invalidates_step_carry():
    n = 8
    data = federated_classification(n, seed=0, n_per_client=16)
    sim = SimConfig(num_clients=n, local_steps=2, batch_size=8)
    trainer = make_trainer(sim, data, donate=True)
    from repro.fl.classifier import init_classifier
    params = init_classifier(jax.random.key(0), dim=data.x.shape[-1],
                             num_classes=data.num_classes)
    caches = core.init_caches(params, n)
    steps = jnp.full((n,), 2, jnp.int32)
    stop = jnp.full((n,), 1 << 20, jnp.int32)
    trainer(params, caches, jnp.zeros((n,), bool), steps, stop,
            jnp.full((n,), 2, jnp.int32))
    assert steps.is_deleted()          # donated (N,) step-count carry
    assert not stop.is_deleted()       # everything else stays live
    assert not any(l.is_deleted() for l in jax.tree.leaves(caches))


def test_engine_donation_trajectory_unchanged():
    """donate_buffers flips allocation behavior only — same History."""
    import dataclasses
    n = 16
    data = federated_classification(n, seed=1, n_per_client=32)
    sim = SimConfig(num_clients=n, rounds=3, seed=1, local_steps=2)
    fl = FLConfig(num_clients=n, clients_per_round=6)
    ref = FleetEngine(data, sim, fl).run("flude")
    fl_d = dataclasses.replace(fl, donate_buffers=True)
    engine = FleetEngine(data, sim, fl_d)
    h1 = engine.run("flude")
    h2 = engine.run("flude")           # template survives donation
    assert h1.acc == ref.acc and h2.acc == ref.acc
    assert h1.received == ref.received and h2.received == ref.received


def test_server_step_memory_donation_reduces_peak():
    """The compiled-step memory profile shows donation aliasing the
    persistent fleet state into the outputs (the bench's peak-live
    metric)."""
    import dataclasses
    n = 32
    data = federated_classification(n, seed=0, n_per_client=16)
    sim = SimConfig(num_clients=n, rounds=1, seed=0, local_steps=2)
    fl = FLConfig(num_clients=n, clients_per_round=8)
    m_off = FleetEngine(data, sim, fl).server_step_memory()
    fl_d = dataclasses.replace(fl, donate_buffers=True)
    m_on = FleetEngine(data, sim, fl_d).server_step_memory()
    assert m_off["alias_bytes"] == 0
    assert m_on["alias_bytes"] > 0
    assert m_on["peak_live_bytes"] < m_off["peak_live_bytes"]


def test_engine_rejects_uneven_mesh():
    n = 10
    data = federated_classification(n, seed=0, n_per_client=16)
    sim = SimConfig(num_clients=n, rounds=1, seed=0)
    fl = FLConfig(num_clients=n, clients_per_round=4, mesh_shape=(4,))
    with pytest.raises(ValueError, match="does not divide"):
        FleetEngine(data, sim, fl)


def test_force_host_device_count_guards_late_calls():
    from repro.launch.mesh import force_host_platform_device_count
    n_now = len(jax.devices())
    before = os.environ.get("XLA_FLAGS")
    try:
        # matching the already-initialized count passes; any other count
        # must raise (the backend can no longer honor the flag)
        force_host_platform_device_count(n_now)
        with pytest.raises(RuntimeError, match="after jax was initialized"):
            force_host_platform_device_count(n_now + 7)
    finally:
        if before is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = before
