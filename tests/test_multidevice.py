"""Multi-device sharding correctness via a subprocess with 8 host devices.

The main pytest process keeps 1 device (per the dry-run isolation rule);
these tests fork a python with XLA_FLAGS=--xla_force_host_platform_device_count=8
and check that the sharded cross-silo step agrees with the single-device
step numerically.
"""
import json
import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.fl import cross_silo
from repro.models import ExecConfig, build_model
from repro.optim.optimizers import make_optimizer
from repro.sharding import partitioning as SP

cfg = get_config("qwen2-7b").reduced(num_kv_heads=2, num_heads=4)
model = build_model(cfg)
tc = TrainConfig(learning_rate=1e-2, warmup_steps=0)
opt = make_optimizer(tc)
params = model.init(jax.random.key(0))
state = cross_silo.TrainState(params, opt.init(params),
                              jnp.zeros((), jnp.int32))
B, S = 8, 32
batch = {
    "tokens": jax.random.randint(jax.random.key(1), (B, S), 0,
                                 cfg.vocab_size),
    "labels": jax.random.randint(jax.random.key(2), (B, S), 0,
                                 cfg.vocab_size),
}
w = jnp.array([1.0, 0.5, 0.0, 1.0])

# single-device reference
step1 = jax.jit(cross_silo.make_train_step(model, tc, 4))
s_ref, m_ref = step1(state, batch, w)

# sharded (4 data x 2 model)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
rules = SP.make_rules(cfg, mesh)
ecfg = ExecConfig(mesh=mesh, rules=rules)
pspecs = SP.param_shardings(model.specs, mesh, rules)
from repro.optim.optimizers import OptState
state_sh = cross_silo.TrainState(
    params=pspecs, opt_state=OptState(pspecs, pspecs,
                                      NamedSharding(mesh, P())),
    step=NamedSharding(mesh, P()))
batch_sh = SP.batch_shardings(batch, mesh)
step2 = jax.jit(cross_silo.make_train_step(model, tc, 4, ecfg),
                in_shardings=(state_sh, batch_sh,
                              NamedSharding(mesh, P())))
with mesh:
    s_sh, m_sh = step2(state, batch, w)

err = max(float(jnp.abs(a - b).max()) for a, b in
          zip(jax.tree.leaves(s_ref.params), jax.tree.leaves(s_sh.params)))
print(json.dumps({
    "loss_ref": float(m_ref["loss"]), "loss_sh": float(m_sh["loss"]),
    "max_param_err": err,
    "n_dev": len(jax.devices()),
}))
"""


@pytest.mark.slow
def test_sharded_step_matches_single_device(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_dev"] == 8
    assert abs(rec["loss_ref"] - rec["loss_sh"]) < 1e-3
    assert rec["max_param_err"] < 5e-3


_FLEET_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.data.synthetic import federated_classification
from repro.fl import SimConfig
from repro.fl.runner import make_trainer

# 32 clients sharded 8-ways over the client axis (cross-device cohorts)
data = federated_classification(32, seed=0, n_per_client=32)
sim = SimConfig(num_clients=32, local_steps=4)
trainer = make_trainer(sim, data)

from repro.fl.classifier import init_classifier
import repro.core as core
params = init_classifier(jax.random.key(0), dim=data.x.shape[-1])
stacked = jax.tree.map(lambda a: jnp.broadcast_to(a, (32,) + a.shape), params)
caches = core.init_caches(params, 32)._replace(params=stacked)
resume = jnp.ones((32,), bool)     # start from the stacked cached states
steps = jnp.full((32,), 4, jnp.int32)
stop = jnp.full((32,), 1 << 20, jnp.int32)
cache_every = jnp.full((32,), 2, jnp.int32)

ref = trainer(params, caches, resume, steps, stop, cache_every)

mesh = jax.make_mesh((8,), ("clients",), axis_types=(AxisType.Auto,))
shard = NamedSharding(mesh, P("clients"))
caches_sh = jax.device_put(caches, jax.tree.map(lambda _: shard, caches))
with mesh:
    got = trainer(params, caches_sh, jax.device_put(resume, shard),
                  jax.device_put(steps, shard),
                  jax.device_put(stop, shard),
                  jax.device_put(cache_every, shard))

err = max(float(jnp.abs(a - b).max()) for a, b in
          zip(jax.tree.leaves(ref[0]), jax.tree.leaves(got[0])))
print(json.dumps({"err": err, "n_dev": len(jax.devices()),
                  "shards": len(jax.tree.leaves(got[0])[0].sharding.device_set)}))
"""


@pytest.mark.slow
def test_fleet_trainer_shards_over_client_axis():
    """DESIGN.md §3 cross-device claim: the vmapped fleet trainer runs with
    the client axis sharded across devices, numerically identical."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", _FLEET_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["n_dev"] == 8
    assert rec["shards"] == 8
    assert rec["err"] < 1e-5
