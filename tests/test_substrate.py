"""Substrate tests: optimizers, data pipeline, checkpointing, sharding rules."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import restore_like, save
from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data.synthetic import (batch_iterator, federated_classification,
                                  lm_dataset)
from repro.optim.optimizers import (clip_by_global_norm, global_norm,
                                    make_optimizer, warmup_cosine)


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def _quad_problem():
    target = jnp.array([1.0, -2.0, 3.0])

    def loss(p):
        return jnp.sum((p["x"] - target) ** 2)

    return {"x": jnp.zeros((3,))}, loss, target


@pytest.mark.parametrize("kind", ["sgd", "momentum", "adam", "adamw"])
def test_optimizers_converge(kind):
    params, loss, target = _quad_problem()
    cfg = TrainConfig(optimizer=kind, learning_rate=0.3, weight_decay=0.0,
                      warmup_steps=0, total_steps=10000, grad_clip=0.0)
    opt = make_optimizer(cfg, lr_fn=lambda s: 0.1)
    state = opt.init(params)
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state = opt.step(params, g, state)
    np.testing.assert_allclose(np.asarray(params["x"]),
                               np.asarray(target), atol=0.05)


def test_bf16_moments_still_converge():
    params, loss, target = _quad_problem()
    cfg = TrainConfig(optimizer="adam", moment_dtype="bfloat16",
                      grad_clip=0.0)
    opt = make_optimizer(cfg, lr_fn=lambda s: 0.1)
    state = opt.init(params)
    assert state.mu["x"].dtype == jnp.bfloat16
    for _ in range(300):
        g = jax.grad(loss)(params)
        params, state = opt.step(params, g, state)
    np.testing.assert_allclose(np.asarray(params["x"]),
                               np.asarray(target), atol=0.1)


def test_grad_clip():
    g = {"a": jnp.full((4,), 100.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-5)
    assert float(gn) == 200.0


def test_warmup_cosine_schedule():
    lr = warmup_cosine(1.0, warmup=10, total=110)
    assert float(lr(0)) == 0.0
    np.testing.assert_allclose(float(lr(10)), 1.0, rtol=1e-5)
    assert float(lr(110)) < 0.2
    assert float(lr(60)) < float(lr(11))


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_noniid_partition_properties():
    data = federated_classification(20, num_classes=10,
                                    classes_per_client=2, seed=0)
    assert data.x.shape[0] == 20
    for i in range(20):
        assert len(np.unique(data.y[i])) <= 2       # paper: 2 classes/device
    # all classes represented somewhere
    assert len(np.unique(data.y)) == 10


def test_classification_learnable():
    """A central model on pooled data reaches high accuracy — the task is
    learnable (so FL differences are attributable to the FL layer)."""
    from repro.fl.classifier import clf_accuracy, clf_loss, init_classifier
    data = federated_classification(16, seed=1)
    x = jnp.asarray(data.x.reshape(-1, data.x.shape[-1]))
    y = jnp.asarray(data.y.reshape(-1))
    params = init_classifier(jax.random.key(0), dim=x.shape[-1])
    for _ in range(200):
        g = jax.grad(clf_loss)(params, x, y)
        params = jax.tree.map(lambda p, gg: p - 0.1 * gg, params, g)
    acc = float(clf_accuracy(params, jnp.asarray(data.test_x),
                             jnp.asarray(data.test_y)))
    assert acc > 0.85


def test_lm_dataset_shapes():
    d = lm_dataset(4, vocab_size=512, seq_len=32, n_seq=8, seed=0)
    assert d.tokens.shape == (4, 8, 33)
    assert d.tokens.min() >= 0 and d.tokens.max() < 512


def test_batch_iterator_covers_epoch():
    x = np.arange(100)[:, None].astype(np.float32)
    y = np.arange(100).astype(np.int32)
    it = batch_iterator(x, y, 10, seed=0)
    seen = set()
    for _ in range(10):
        xb, yb = next(it)
        seen.update(yb.tolist())
    assert len(seen) == 100


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)},
            "meta": 7}
    path = os.path.join(tmp_path, "ckpt.msgpack")
    save(path, tree)
    back = restore_like(path, tree)
    np.testing.assert_allclose(np.asarray(back["a"]), np.asarray(tree["a"]))
    assert back["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_restores_train_state(tmp_path):
    from repro.models import build_model
    cfg = get_config("qwen2-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    path = os.path.join(tmp_path, "params.msgpack")
    save(path, params)
    back = restore_like(path, params)
    flat1 = jax.tree.leaves(params)
    flat2 = jax.tree.leaves(back)
    assert all(np.allclose(np.asarray(a, np.float32),
                           np.asarray(b, np.float32))
               for a, b in zip(flat1, flat2))


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

def test_compile_cache_dir(monkeypatch):
    """The environment's JAX_COMPILATION_CACHE_DIR wins; otherwise the
    cache sits at the fixed <repo>/.jax_cache."""
    from pathlib import Path
    from repro.launch import compile_cache as CC
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    assert CC.compile_cache_dir() == CC.REPO_CACHE
    assert CC.REPO_CACHE.name == ".jax_cache"
    assert (CC.REPO_CACHE.parent / "src" / "repro" / "launch"
            / "compile_cache.py").is_file()
    monkeypatch.setenv(CC.ENV_VAR, "/elsewhere/cache")
    assert CC.compile_cache_dir() == Path("/elsewhere/cache")


# ---------------------------------------------------------------------------
# sharding rules (pure logic — no devices needed)
# ---------------------------------------------------------------------------

def test_rules_and_divisibility():
    import jax as _jax
    from repro.sharding import partitioning as SP
    if len(_jax.devices()) < 1:
        pytest.skip("no devices")
    from jax.sharding import AxisType
    mesh = _jax.make_mesh((1, 1), ("data", "model"),
                          axis_types=(AxisType.Auto,) * 2)
    for arch in ("qwen2-7b", "llama3-405b", "mixtral-8x7b",
                 "deepseek-v2-236b", "whisper-large-v3"):
        cfg = get_config(arch)
        rules = SP.make_rules(cfg, mesh)
        assert "embed" in rules and "vocab" in rules


def test_spec_for_axes_no_duplicate_mesh_axes():
    from jax.sharding import PartitionSpec
    from repro.sharding.partitioning import spec_for_axes
    rules = {"embed": ("data",), "mlp": ("model",), "vocab": ("model",)}
    spec = spec_for_axes(("vocab", "mlp"), rules)   # model twice -> once
    flat = [a for part in spec if part is not None
            for a in ((part,) if isinstance(part, str) else part)]
    assert len(flat) == len(set(flat))


def test_attn_tp_axis_choices():
    from repro.sharding.partitioning import _attn_tp_axis
    assert _attn_tp_axis(get_config("llama3-405b"), 16) == "q_group"
    # MLA weights carry a single "heads" axis — sharding kv_heads would
    # leave attention replicated (measured 16× flop waste, §Perf deepseek)
    assert _attn_tp_axis(get_config("deepseek-v2-236b"), 16) == "heads"
    assert _attn_tp_axis(get_config("zamba2-1.2b"), 16) == "kv_heads"
    assert _attn_tp_axis(get_config("qwen2-7b"), 16) is None   # replicate
    assert _attn_tp_axis(get_config("whisper-large-v3"), 16) is None
