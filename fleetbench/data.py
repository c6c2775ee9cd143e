"""The benchmark's federated classification data, made on the device.

A vectorised form of the label-shard task the program's own generator
describes (a Gaussian mixture of ``num_classes`` centres, each client
holding an anchor class ``i % num_classes`` and one other class drawn at
random, test samples drawn uniformly over the classes).  It is made in
one jitted call from the seed, so the same seed gives the same inputs
and nothing is drawn on the host.  The arrays are handed to the program
and to the reference alike; neither makes data of its own.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp


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
