"""Robust server aggregation rules — the ``FLConfig.agg_rule`` axis.

Orthogonal to ``agg_impl`` (xla | pallas | pallas_interpret): a *rule*
decides **what** statistic of the packed (C, D) client buffer becomes
the new global model, an *impl* decides **how** its inner reductions
run.  Rules plug in through a decorator registry mirroring
``repro.fleet.register_dynamics``::

    @register_agg_rule("my-rule")
    class MyRule(AggRule):
        def reduce(self, buf, gvec, weights, *, impl, ...): ...

and are instantiated by name via ``make_agg_rule`` /
``FLConfig.agg_rule`` with the hashable ``agg_rule_params`` pairs.

Built-ins:

* ``mean`` — the staleness-discounted weighted mean (the default; the
  round step keeps its historical direct path, bit-identical).
* ``geometric_median`` — smoothed Weiszfeld (RFA, arXiv 1912.13445)
  over the packed buffer; tolerates up to half the received weight
  being arbitrarily corrupted.
* ``trimmed_mean`` — coordinate-wise trimmed mean.
* ``trust`` — stateful: a per-client (N,) trust score carried in fleet
  state like the Beta beliefs, updated *on device* every round from the
  observed update-deviation norms (cf. FedAR, arXiv 2101.03705) and
  multiplied into the aggregation weights.  Zero per-round host syncs.

Interface contract: ``reduce(buf, gvec, weights, ...)`` gets the packed
(C, D) fp32 client rows, the packed (D,) previous global vector and the
*unnormalized* (C,) aggregation weights (zero = not received) and
returns the (D,) aggregated vector; the caller applies the empty-round
gate and unpacks.  Stateful rules implement ``reduce_stateful`` taking
and returning the (C,)-aligned state rows (the round step
gathers/scatters them on the cohort path).
"""
from __future__ import annotations

from typing import Dict, Tuple, Type

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.fed_agg.ops import (fed_agg_packed,
                                       fed_agg_packed_sharded)
from repro.kernels.robust_agg.ops import (geometric_median,
                                          geometric_median_sharded,
                                          masked_median, residual_norms,
                                          trimmed_mean,
                                          trimmed_mean_sharded)
from repro.sharding.partitioning import fleet_axis_size

TINY = 1e-30


class AggRule:
    """Robust aggregation rule: static params + a pure packed reduction.

    ``reduce`` must be jittable; the fused server round step traces it
    once.  ``stateful=True`` rules add a per-client state row threaded
    through rounds by the engine (see ``TrustRule``).
    """
    name = "base"
    stateful = False

    def __init__(self, **params):
        self.params = dict(params)

    def reduce(self, buf, gvec, weights, *, impl="xla", block_c=8,
               block_d=2048, mesh=None, axis="clients"):
        raise NotImplementedError

    # -- stateful extension -------------------------------------------------
    def init_state(self, num_clients: int):
        raise NotImplementedError(f"agg rule {self.name!r} is stateless")

    def reduce_stateful(self, buf, gvec, weights, state, *, impl="xla",
                        block_c=8, block_d=2048, mesh=None,
                        axis="clients"):
        raise NotImplementedError(f"agg rule {self.name!r} is stateless")


def _sharded(mesh) -> bool:
    return mesh is not None and fleet_axis_size(mesh) > 1


class MeanRule(AggRule):
    """The weighted mean — exactly the reduction the historical round
    step runs (the step still calls it directly when ``agg_rule="mean"``
    so the default path's jaxpr never changes; this class serves the
    registry, tests and direct callers)."""

    def reduce(self, buf, gvec, weights, *, impl="xla", block_c=8,
               block_d=2048, mesh=None, axis="clients"):
        w = weights.astype(jnp.float32)
        w_norm = w / jnp.maximum(w.sum(), TINY)
        if _sharded(mesh):
            return fed_agg_packed_sharded(buf, w_norm, mesh=mesh,
                                          axis=axis, impl=impl,
                                          block_c=block_c, block_d=block_d)
        return fed_agg_packed(buf, w_norm, impl=impl, block_c=block_c,
                              block_d=block_d)


class GeometricMedianRule(AggRule):
    """Smoothed Weiszfeld geometric median (RFA)."""

    def __init__(self, iters: int = 6, eps: float = 1e-6):
        super().__init__(iters=int(iters), eps=float(eps))
        self.iters = int(iters)
        self.eps = float(eps)

    def reduce(self, buf, gvec, weights, *, impl="xla", block_c=8,
               block_d=2048, mesh=None, axis="clients"):
        if _sharded(mesh):
            return geometric_median_sharded(
                buf, weights, mesh=mesh, axis=axis, iters=self.iters,
                eps=self.eps, impl=impl, block_c=block_c, block_d=block_d)
        return geometric_median(buf, weights, iters=self.iters,
                                eps=self.eps, impl=impl, block_c=block_c,
                                block_d=block_d)


class TrimmedMeanRule(AggRule):
    """Coordinate-wise weighted trimmed mean."""

    def __init__(self, trim: float = 0.2):
        super().__init__(trim=float(trim))
        self.trim = float(trim)

    def reduce(self, buf, gvec, weights, *, impl="xla", block_c=8,
               block_d=2048, mesh=None, axis="clients"):
        if _sharded(mesh):
            return trimmed_mean_sharded(buf, weights, mesh=mesh,
                                        axis=axis, trim=self.trim)
        return trimmed_mean(buf, weights, trim=self.trim)


class TrustRule(AggRule):
    """Trust-weighted mean with on-device trust learning.

    Every round, each received client's deviation norm
    ``dist_c = ||u_c - g||`` is compared against the received-set median
    (a robust scale reference): ``score_c = (ref / max(dist_c, ref))
    ** power`` is 1 for typical updates and falls quadratically for
    outliers.  Trust is an EMA ``t <- (1 - eta) * t + eta * score`` over
    the rounds a client reports, and the aggregation weight becomes
    ``w_c * clip(t_c, floor, 1)`` — persistent outliers fade to the
    ``floor`` weight, mirroring how the Beta beliefs fade undependable
    devices out of *selection*.  The (N,) trust vector lives in fleet
    state on device; nothing syncs per round.
    """
    stateful = True

    def __init__(self, eta: float = 0.3, floor: float = 0.05,
                 power: float = 2.0, init: float = 1.0):
        super().__init__(eta=float(eta), floor=float(floor),
                         power=float(power), init=float(init))
        self.eta = float(eta)
        self.floor = float(floor)
        self.power = float(power)
        self.init = float(init)

    def init_state(self, num_clients: int):
        import numpy as np
        return np.full((num_clients,), self.init, np.float32)

    def _update(self, dist, weights, state, ref):
        valid = weights > 0
        ref = jnp.maximum(ref, 1e-12)
        score = (ref / jnp.maximum(dist, ref)) ** self.power
        return jnp.where(valid, (1.0 - self.eta) * state
                         + self.eta * score, state)

    def reduce_stateful(self, buf, gvec, weights, state, *, impl="xla",
                        block_c=8, block_d=2048, mesh=None,
                        axis="clients"):
        if _sharded(mesh):
            def body(w_blk, u_blk, g_rep, t_blk):
                w = w_blk.astype(jnp.float32)
                dist = residual_norms(u_blk, g_rep, impl=impl,
                                      block_c=block_c, block_d=block_d)
                dg = jax.lax.all_gather(dist, axis, tiled=True)
                wg = jax.lax.all_gather(w, axis, tiled=True)
                ref = masked_median(dg, wg > 0)
                new_t = self._update(dist, w, t_blk, ref)
                w_eff = w * jnp.clip(new_t, self.floor, 1.0)
                wsum = jax.lax.psum(w_eff.sum(), axis)
                vec = jax.lax.psum(
                    fed_agg_packed(u_blk, w_eff / jnp.maximum(wsum, TINY),
                                   impl=impl, block_c=block_c,
                                   block_d=block_d).astype(jnp.float32),
                    axis)
                return vec, new_t

            return jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(axis), P(axis, None), P(None), P(axis)),
                out_specs=(P(), P(axis)),
                check_vma=False)(weights, buf, gvec, state)

        w = weights.astype(jnp.float32)
        dist = residual_norms(buf, gvec, impl=impl, block_c=block_c,
                              block_d=block_d)
        ref = masked_median(dist, w > 0)
        new_state = self._update(dist, w, state, ref)
        w_eff = w * jnp.clip(new_state, self.floor, 1.0)
        vec = fed_agg_packed(buf, w_eff / jnp.maximum(w_eff.sum(), TINY),
                             impl=impl, block_c=block_c, block_d=block_d)
        return vec, new_state


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type[AggRule]] = {}


def register_agg_rule(name: str, *, allow_override: bool = False):
    """Class decorator: ``@register_agg_rule("huber")`` makes the rule
    constructible by name through ``make_agg_rule`` /
    ``FLConfig.agg_rule``."""
    def deco(cls: Type[AggRule]) -> Type[AggRule]:
        if not (isinstance(cls, type) and issubclass(cls, AggRule)):
            raise TypeError(f"@register_agg_rule expects an AggRule "
                            f"subclass, got {cls!r}")
        if name in _REGISTRY and not allow_override:
            raise ValueError(f"agg rule {name!r} already registered "
                             f"(pass allow_override=True to replace)")
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_agg_rule(name: str) -> Type[AggRule]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown agg rule {name!r}; registered: "
                       f"{', '.join(available_agg_rules())}") from None


def available_agg_rules():
    return sorted(_REGISTRY)


def make_agg_rule(name: str, params: Tuple = ()) -> AggRule:
    """Instantiate a registered rule.  ``params`` is the hashable
    ``FLConfig.agg_rule_params`` tuple of ``(key, value)`` pairs."""
    return get_agg_rule(name)(**dict(params))


register_agg_rule("mean")(MeanRule)
register_agg_rule("geometric_median")(GeometricMedianRule)
register_agg_rule("trimmed_mean")(TrimmedMeanRule)
register_agg_rule("trust")(TrustRule)
