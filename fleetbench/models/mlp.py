"""The MLP classifier's data, plain reference and counts.

A configuration whose ``model`` block says ``"kind": "mlp"`` is trained
on this file's data and checked against this file's model.  The
interface every ``models/<kind>.py`` provides (``harness.MODEL_API``):

* ``make_data(seed, data_spec)``  the federated data, made on the
  device in one jitted call from the seed: an object with ``x``, ``y``
  (per-client arrays leading with the client axis), ``test_x``,
  ``test_y`` and ``num_classes``.  The program and the reference are
  both handed these arrays; neither makes data of its own;
* ``leaf_shapes(model)``  ``{name: shape}`` of the trainable leaves, in
  the order the program's pytree flattens them;
* ``init_params(seed, model)``  ``(trainable, frozen)`` leaf dicts;
* ``loss_fn(trainable, frozen, x, y, model)``  the mean loss over one
  client's batch, matrix products at ``HIGHEST``;
* ``train_flops(model, samples)``, ``eval_flops(model, samples)`` and
  ``packed_dim(model)`` (trainable parameters, the packed row length).

The data is a vectorised form of the label-shard task the program's own
generator describes (a Gaussian mixture of ``num_classes`` centres, each
client holding an anchor class ``i % num_classes`` and one other class
drawn at random, test samples drawn uniformly over the classes).  The
model is a tanh MLP with a softmax cross-entropy loss; all its leaves
are trainable.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Data
# ---------------------------------------------------------------------------

class Data(NamedTuple):
    x: jax.Array            # (N, n_per_client, dim) float32
    y: jax.Array            # (N, n_per_client) int32
    test_x: jax.Array       # (n_test, dim) float32
    test_y: jax.Array       # (n_test,) int32
    num_classes: int


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7))
def _make(key, num_clients, num_classes, dim, n_per_client, n_test,
          margin, noise):
    k_c, k_rest, k_pick, k_x, k_ty, k_tx = jax.random.split(key, 6)
    centers = jax.random.normal(k_c, (num_classes, dim)) * margin
    anchor = jnp.arange(num_clients, dtype=jnp.int32) % num_classes
    rest = (anchor + 1 + jax.random.randint(
        k_rest, (num_clients,), 0, num_classes - 1)) % num_classes
    pick = jax.random.bernoulli(k_pick, 0.5, (num_clients, n_per_client))
    y = jnp.where(pick, anchor[:, None], rest[:, None]).astype(jnp.int32)
    x = centers[y] + noise * jax.random.normal(
        k_x, (num_clients, n_per_client, dim))
    ty = jax.random.randint(k_ty, (n_test,), 0, num_classes)
    tx = centers[ty] + noise * jax.random.normal(k_tx, (n_test, dim))
    return x.astype(jnp.float32), y, tx.astype(jnp.float32), \
        ty.astype(jnp.int32)


def make_data(seed: int, spec: dict) -> Data:
    """The data of a configuration's ``data`` block, from ``seed``."""
    key = jax.random.fold_in(jax.random.key(seed), 0xDA7A)
    x, y, tx, ty = _make(
        key, int(spec["num_clients"]), int(spec["num_classes"]),
        int(spec["dim"]), int(spec["n_per_client"]), int(spec["n_test"]),
        float(spec["margin"]), float(spec["noise"]))
    return Data(x, y, tx, ty, int(spec["num_classes"]))


# ---------------------------------------------------------------------------
# Model: tanh hidden layers, softmax cross-entropy
# ---------------------------------------------------------------------------

def leaf_names(depth: int) -> List[str]:
    """Leaves in the order a sorted-key pytree flattens them."""
    names = []
    for i in range(depth):
        names += [f"h{i}/b", f"h{i}/w"]
    return names + ["out/b", "out/w"]


def leaf_shapes(model: dict) -> Dict[str, tuple]:
    dim, hidden = int(model["dim"]), int(model["hidden"])
    depth, classes = int(model["depth"]), int(model["num_classes"])
    shapes, d_in = {}, dim
    for i in range(depth):
        shapes[f"h{i}/w"] = (d_in, hidden)
        shapes[f"h{i}/b"] = (hidden,)
        d_in = hidden
    shapes["out/w"] = (d_in, classes)
    shapes["out/b"] = (classes,)
    return {k: shapes[k] for k in leaf_names(depth)}


def init_params(seed: int, model: dict):
    """Fan-in scaled normal weights, zero biases, one split key per leaf
    in flattening order, from ``key(seed + 1)``; nothing is frozen."""
    shapes = leaf_shapes(model)
    keys = jax.random.split(jax.random.key(int(seed) + 1), len(shapes))
    out = {}
    for k, (name, shape) in zip(keys, shapes.items()):
        if name.endswith("/b"):
            out[name] = jnp.zeros(shape, jnp.float32)
        else:
            std = 1.0 / np.sqrt(max(shape[0], 1))
            out[name] = jax.random.normal(k, shape, jnp.float32) * std
    return out, {}


def logits(params, x, depth: int):
    h = x
    for i in range(depth):
        h = jnp.tanh(jnp.dot(h, params[f"h{i}/w"], precision=HIGHEST)
                     + params[f"h{i}/b"])
    return jnp.dot(h, params["out/w"], precision=HIGHEST) + params["out/b"]


def loss_fn(trainable, frozen, x, y, model: dict):
    lp = jax.nn.log_softmax(logits(trainable, x, int(model["depth"])),
                            axis=-1)
    return -jnp.take_along_axis(lp, y[:, None], axis=-1).mean()


# ---------------------------------------------------------------------------
# Counts, from shapes alone
# ---------------------------------------------------------------------------

def mlp_macs(model: dict) -> int:
    """Multiply-accumulates of one sample through the classifier."""
    d, h = int(model["dim"]), int(model["hidden"])
    depth, k = int(model["depth"]), int(model["num_classes"])
    return d * h + (depth - 1) * h * h + h * k


def packed_dim(model: dict) -> int:
    """Parameters of the classifier (the packed row length D)."""
    d, h = int(model["dim"]), int(model["hidden"])
    depth, k = int(model["depth"]), int(model["num_classes"])
    return d * h + h + (depth - 1) * (h * h + h) + h * k + k


def train_flops(model: dict, samples: int) -> float:
    """Forward and backward passes of ``samples`` samples: 2 FLOPs per
    MAC forward, 4 backward (input and weight gradients); biases and
    activations are not counted."""
    return 6.0 * mlp_macs(model) * samples


def eval_flops(model: dict, samples: int) -> float:
    return 2.0 * mlp_macs(model) * samples
