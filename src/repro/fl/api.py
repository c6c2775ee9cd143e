"""Typed policy API for the cross-device FL runner.

The server policy loop — dependability-aware selection (Alg. 1),
staleness-aware distribution (Eq. 4), cache resume (C3) — speaks three
typed, jit-friendly pytree dataclasses instead of string-keyed dicts:

* ``RoundPlan``      — what the server decides *before* a round (who is
                       selected, who gets a fresh model, who resumes from
                       cache, the receive quorum, optional per-device step
                       counts and aggregation-weight multipliers);
* ``RoundObservation`` — what a policy may look at when planning (round
                       index, online mask, the device-resident caches,
                       static fleet features);
* ``RoundReport``    — what actually happened (received/fail masks, local
                       losses, per-device finish times, billed duration).

A ``Policy`` is a thin object holding static configuration; all mutable
state lives in an explicit ``PolicyState`` threaded through pure(-ish)
``plan``/``observe`` transitions so the engine — not the policy — owns the
loop.  Policies plug in through a decorator registry::

    @register_policy("my-policy")
    class MyPolicy(Policy):
        ...

and are instantiated by name via ``make_policy`` — no runner edits needed.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core.caching import ClientCaches
from repro.fl.simulator import Fleet, SimConfig

_BOOL_FIELDS = ("selected", "distribute", "resume")


def cohort_index(selected, cohort_size: int) -> jax.Array:
    """Device cohort index of a selection mask: the ascending client ids
    of the selected set, padded to the static ``cohort_size`` with the
    out-of-range sentinel N (= ``selected.shape[0]``).

    Traceable (fixed output shape), so the engine derives it on device
    in a small jit of its own — no host sync.  Sentinel entries make every
    ``mode="fill"`` gather read a benign default and every
    ``mode="drop"`` scatter skip the row, which is what keeps the compact
    (X, ...) round path bit-identical to the full scan.  When more than
    ``cohort_size`` clients are selected the index silently truncates to
    the lowest ids — pair with :func:`cohort_overflow` (the engine defers
    the flag through its round ledger and raises at readback).
    """
    sel = jnp.asarray(selected)
    return jnp.flatnonzero(sel, size=cohort_size,
                           fill_value=sel.shape[0])


def cohort_overflow(selected, cohort_size: int) -> jax.Array:
    """Device bool scalar: did the plan select more clients than the
    static cohort can hold (i.e. did :func:`cohort_index` truncate)?"""
    return jnp.asarray(selected).sum() > cohort_size


# ---------------------------------------------------------------------------
# Typed round messages
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """Server-side decisions for one round (a jit-able pytree).

    selected/distribute/resume: (N,) bool masks.  ``quorum`` is the receive
    cutoff — the round closes after that many successful uploads (§4.4
    Alg. 2 line 15).  ``steps_override`` (optional, (N,) int) replaces the
    uniform ``local_steps`` workload; ``agg_weights`` (optional, (N,)
    float) multiplies the server aggregation weights.
    """
    selected: Any
    distribute: Any
    resume: Any
    quorum: Any
    steps_override: Optional[Any] = None
    agg_weights: Optional[Any] = None

    @classmethod
    def create(cls, selected, distribute, resume, quorum,
               steps_override=None, agg_weights=None,
               num_clients: Optional[int] = None) -> "RoundPlan":
        """Canonicalize + validate.  Host-side entry point: accepts numpy
        or jax arrays, coerces mask dtypes to bool, and runs the full
        shape/value validation (use the bare constructor inside jit where
        values are abstract)."""
        plan = cls(selected=np.asarray(selected, bool)
                   if not isinstance(selected, jax.Array)
                   else selected.astype(bool),
                   distribute=np.asarray(distribute, bool)
                   if not isinstance(distribute, jax.Array)
                   else distribute.astype(bool),
                   resume=np.asarray(resume, bool)
                   if not isinstance(resume, jax.Array)
                   else resume.astype(bool),
                   quorum=float(quorum),
                   steps_override=steps_override,
                   agg_weights=agg_weights)
        plan.validate(num_clients)
        object.__setattr__(plan, "_validated", True)
        return plan

    @classmethod
    def device(cls, selected, distribute, resume, quorum,
               steps_override=None, agg_weights=None) -> "RoundPlan":
        """Device-native construction for jnp policies.

        Runs the *structural* checks only (1-D bool masks of one length,
        int/float optionals of the same length) — shape and dtype are
        array metadata, so nothing syncs and ``quorum`` stays a device
        scalar.  This is what keeps a jitted policy's ``plan`` a pure
        dispatch: the engine's pipelined device loop can enqueue the
        round without draining the device queue.  The value invariants
        (quorum ≤ |selected|, resume ⊆ selected, override ≤ the trainer's
        scan length) are the caller's responsibility — built-in device
        policies guarantee them by construction, and the engine clamps
        the workload regardless.
        """
        plan = cls(selected=selected, distribute=distribute, resume=resume,
                   quorum=quorum, steps_override=steps_override,
                   agg_weights=agg_weights)
        n = plan._check_structure()
        if getattr(quorum, "ndim", 0) != 0:
            raise ValueError(
                f"RoundPlan.quorum must be a scalar, got shape "
                f"{getattr(quorum, 'shape', None)} — a non-scalar quorum "
                f"broadcasts through the jitted round cut and only fails "
                f"rounds later at ledger readback")
        if steps_override is not None and (
                getattr(steps_override, "shape", None) != (n,)
                or not np.issubdtype(np.dtype(steps_override.dtype),
                                     np.integer)):
            raise ValueError(
                f"RoundPlan.steps_override must be ({n},) int, got shape "
                f"{getattr(steps_override, 'shape', None)} dtype "
                f"{getattr(steps_override, 'dtype', None)}")
        if agg_weights is not None and \
                getattr(agg_weights, "shape", None) != (n,):
            raise ValueError(
                f"RoundPlan.agg_weights must be ({n},), got "
                f"{getattr(agg_weights, 'shape', None)}")
        object.__setattr__(plan, "_validated", True)
        return plan

    def _check_structure(self, num_clients: Optional[int] = None) -> int:
        """Shape/dtype checks on array metadata (no value sync)."""
        n = num_clients
        for name in _BOOL_FIELDS:
            arr = getattr(self, name)
            if arr is None:
                raise ValueError(f"RoundPlan.{name} is required")
            if getattr(arr, "ndim", None) != 1:
                raise ValueError(f"RoundPlan.{name} must be a 1-D mask, "
                                 f"got shape {getattr(arr, 'shape', None)}")
            if np.dtype(arr.dtype) != np.bool_:
                raise ValueError(f"RoundPlan.{name} must be bool, got "
                                 f"{arr.dtype}")
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise ValueError(
                    f"RoundPlan.{name} has {arr.shape[0]} entries, "
                    f"expected {n}")
        return n

    def validate(self, num_clients: Optional[int] = None,
                 local_steps: Optional[int] = None) -> "RoundPlan":
        """Shape/dtype/value checks on concrete (host) values.

        Raises ``ValueError`` on malformed plans; returns self so calls
        chain.  ``local_steps`` (when given) caps ``steps_override`` at
        the trainer's scan length: requesting more work than the trainer
        can run would silently truncate training while the timing model
        charges the full request.  Under tracing the value checks are
        skipped (abstract arrays have no concrete sums)."""
        n = self._check_structure(num_clients)
        if isinstance(self.selected, jax.core.Tracer):
            return self
        n_sel = int(np.asarray(self.selected).sum())
        q = float(self.quorum)
        if q < 0:
            raise ValueError(f"RoundPlan.quorum must be >= 0, got {q}")
        if q > n_sel:
            raise ValueError(
                f"RoundPlan.quorum ({q}) exceeds the selected count "
                f"({n_sel}) — the round could never close on uploads")
        if n_sel > 0 and q < 1:
            raise ValueError(
                "RoundPlan.quorum must be >= 1 when any device is "
                "selected — a zero quorum idle-waits the full deadline")
        if np.asarray(self.resume & ~self.selected).any():
            raise ValueError("RoundPlan.resume must be a subset of "
                             "RoundPlan.selected")
        if self.steps_override is not None:
            so = np.asarray(self.steps_override)
            if so.shape != (n,) or not np.issubdtype(so.dtype, np.integer):
                raise ValueError(
                    f"RoundPlan.steps_override must be (N,) int, got "
                    f"shape {so.shape} dtype {so.dtype}")
            if (so < 0).any():
                raise ValueError("RoundPlan.steps_override must be >= 0")
            if local_steps is not None and so.size \
                    and int(so.max()) > local_steps:
                raise ValueError(
                    f"RoundPlan.steps_override requests up to "
                    f"{int(so.max())} local steps but the trainer scans "
                    f"only {local_steps} — the excess would silently not "
                    f"run while the timing model charged it")
        if self.agg_weights is not None:
            w = np.asarray(self.agg_weights, np.float32)
            if w.shape != (n,):
                raise ValueError(
                    f"RoundPlan.agg_weights must be (N,), got {w.shape}")
            if not np.isfinite(w).all() or (w < 0).any():
                raise ValueError(
                    "RoundPlan.agg_weights must be finite and >= 0")
        return self

    def cohort_index(self, cohort_size: int) -> jax.Array:
        """This plan's device cohort-index view (see module-level
        :func:`cohort_index`): ascending selected client ids padded with
        the sentinel N to the static ``cohort_size``."""
        return cohort_index(self.selected, cohort_size)


@dataclasses.dataclass(frozen=True)
class RoundReport:
    """What happened in one round, fed back to ``Policy.observe``.

    received: (N,) bool — uploaded before the cutoff.
    fail:     (N,) bool — interrupted mid-round (undependability draw).
    losses:   (N,) float — mean local training loss (garbage for idle).
    durations:(N,) float — per-device finish time, inf if never uploaded.
    duration: float — billed round wall clock (cutoff or deadline).
    rnd:      int — round index.

    On the legacy host-RNG path the array fields are numpy and
    ``duration`` is a python float.  On the device round path everything
    but ``rnd`` is a device array (``duration`` a float32 device scalar —
    the jitted round cut never syncs; rounds that idle-waited the
    deadline carry the float32-*nearest* cast of ``round_deadline``,
    which may sit one ulp above it, while History bills the exact f64
    config value): jnp-native policies fold the
    report in as one more dispatch, which is what keeps the pipelined
    loop (``FLConfig.pipeline_depth`` > 1) free of per-round host
    blocking; host-side policies pay one ``np.asarray`` sync as before.
    """
    received: Any
    fail: Any
    losses: Any
    durations: Any
    duration: float
    rnd: int


@dataclasses.dataclass(frozen=True)
class RoundObservation:
    """What a policy may read when planning round ``rnd``.

    ``caches`` stays device-resident — jnp-native policies (flude, safa)
    consume it directly; host-side policies pull the (N,) metadata only.
    ``draw`` is the round's device-resident fleet draw when a
    ``repro.fleet`` dynamics process produced it (None on the legacy
    host-RNG path): jnp-native policies read ``draw.online`` /
    ``draw.bandwidth`` / ``draw.battery`` directly instead of re-uploading
    the host mask.  On that path ``online`` is the *device* mask
    (``draw.online`` itself — reading it eagerly would stall the
    pipelined loop); host-side policies convert with ``np.asarray`` at
    their own sync point.
    """
    rnd: int
    online: Any                # (N,) bool — numpy, or jax on the device path
    caches: ClientCaches
    draw: Optional[Any] = None


for _cls, _data in ((RoundPlan, ["selected", "distribute", "resume",
                                 "quorum", "steps_override",
                                 "agg_weights"]),
                    (RoundReport, ["received", "fail", "losses",
                                   "durations"]),):
    jax.tree_util.register_dataclass(
        _cls, data_fields=_data,
        meta_fields=[f.name for f in dataclasses.fields(_cls)
                     if f.name not in _data])


# ---------------------------------------------------------------------------
# Policy protocol
# ---------------------------------------------------------------------------

class Policy:
    """Server-side policy: static config + pure state transitions.

    ``init_state`` builds the policy's mutable state (host RNGs, belief
    arrays, ...).  ``plan`` maps (state, observation, jax rng) to
    (state', RoundPlan); ``observe`` folds a RoundReport back into the
    state.  Subclasses override the three methods and the class flags.
    """
    name = "base"
    uses_cache = False            # wants the C3 client cache machinery
    waits_for_stragglers = True   # sync designs idle-wait to the deadline
    selects_at_most_clients_per_round = False
    # ^ static trait: every plan's selected count is bounded by
    #   FLConfig.clients_per_round (flude/random/oort/safa/fedsea).
    #   Select-all designs (mifa, asyncfeded) leave it False — their
    #   bound is the fleet size.

    def __init__(self, sim_cfg: SimConfig, fl_cfg: FLConfig,
                 fleet: Optional[Fleet] = None, mesh: Any = None):
        self.sim_cfg = sim_cfg
        self.fl_cfg = fl_cfg
        self.fleet = fleet
        # fleet mesh ("clients" axis) the engine runs under — policies that
        # keep (N,) device-resident state place it sharded over this
        self.mesh = mesh

    def init_state(self) -> Any:
        return None

    def selection_bound(self) -> int:
        """Static upper bound on any plan's selected count — what the
        engine checks ``FLConfig.cohort_size`` against up front (a cohort
        smaller than a plan's selection would silently truncate
        training).  Derived from ``selects_at_most_clients_per_round``;
        override for policies with a different static bound."""
        n = self.fl_cfg.num_clients
        if self.selects_at_most_clients_per_round:
            return min(self.fl_cfg.clients_per_round, n)
        return n

    def plan(self, state: Any, obs: RoundObservation,
             rng) -> Tuple[Any, RoundPlan]:
        raise NotImplementedError

    def observe(self, state: Any, plan: RoundPlan,
                report: RoundReport) -> Any:
        return state

    def history_extras(self, state: Any) -> Dict[str, Any]:
        """Optional end-of-run diagnostics merged into ``History``."""
        return {}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type[Policy]] = {}


def register_policy(name: str, *, allow_override: bool = False):
    """Class decorator: ``@register_policy("flude")`` makes the policy
    constructible by name through ``make_policy`` / ``FleetEngine.run``."""
    def deco(cls: Type[Policy]) -> Type[Policy]:
        if not (isinstance(cls, type) and issubclass(cls, Policy)):
            raise TypeError(f"@register_policy expects a Policy subclass, "
                            f"got {cls!r}")
        if name in _REGISTRY and not allow_override:
            raise ValueError(f"policy {name!r} already registered "
                             f"(pass allow_override=True to replace)")
        cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_policy(name: str) -> Type[Policy]:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown policy {name!r}; registered: "
                       f"{', '.join(available_policies())}") from None


def available_policies():
    return sorted(_REGISTRY)


def make_policy(name: str, sim_cfg: SimConfig, fl_cfg: FLConfig,
                fleet: Optional[Fleet] = None, mesh: Any = None) -> Policy:
    return get_policy(name)(sim_cfg, fl_cfg, fleet, mesh=mesh)
