"""The six built-in server policies, ported to the typed registry API.

FLUDE (the paper) plus the five comparison baselines.  Each policy keeps
its mutable per-run state in an explicit ``PolicyState`` returned by
``init_state`` and threaded through ``plan``/``observe`` — the engine owns
the loop.  flude/safa/asyncfeded plan from device-resident cache metadata;
oort/fedsea are inherently host-side (numpy utility bookkeeping) and stay
so behind the same typed interface.

Caveat on purity: states that carry a ``np.random.RandomState`` (random,
oort, safa, fedsea) advance it *in place* inside ``plan`` — the typed
transitions are pure in their array fields but the host RNG is a cursor,
matching the historical runner's draw sequence exactly.  Replaying a
retained state re-draws fresh randomness; speculative/pipelined planning
over these policies must checkpoint the RandomState explicitly
(``state.get_state()``/``set_state``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.fl.api import (Policy, RoundObservation, RoundPlan, RoundReport,
                          register_policy)
from repro.fl.simulator import place_per_client

BIG = 1 << 20


# ---------------------------------------------------------------------------
# FLUDE (paper §4, Algorithms 1–2)
# ---------------------------------------------------------------------------

class FludePolicyState(NamedTuple):
    core: core.FludeState
    last: Optional[core.FludePlan]     # plan pending its belief update
    # the raw receive mask of ``last``'s round, parked dispatch-free by
    # ``observe`` and folded into the *next* round's plan dispatch (or
    # flushed at run end) — Eq. 1 bookkeeping costs zero extra dispatches
    pending_received: Optional[jax.Array] = None


# Alg. 1/2 planning and Eq. 1/3 bookkeeping are pure jnp over fixed-shape
# fleet arrays — one jitted dispatch per round each, instead of the old
# runner's eager op-by-op evaluation.  Memoized per config so repeated
# short runs (test suites, policy sweeps) never re-trace; bounded so a
# config sweep doesn't pin compiled executables for the process lifetime.
def _plan_body(st, caches, online, rng, hints, fl_cfg, with_hints):
    p = core.plan_round(st, caches, online, fl_cfg, rng,
                        explore_hints=hints if with_hints else None)
    # quorum clamp (can't wait for more receipts than selections)
    # fused into the plan dispatch: eager it is three op-by-op
    # round-trips per round; the f32 minimum here equals the host
    # path's float() min bit-for-bit (both operands are exact f32)
    q = jnp.minimum(p.quorum, p.selected.sum().astype(jnp.float32))
    return p._replace(quorum=q)


@functools.lru_cache(maxsize=8)
def _flude_plan_jit(fl_cfg, with_hints: bool):
    def flude_plan(st, caches, online, rng, hints):
        return _plan_body(st, caches, online, rng, hints, fl_cfg,
                          with_hints)
    return jax.jit(flude_plan)


@functools.lru_cache(maxsize=8)
def _flude_update_plan_jit(fl_cfg, with_hints: bool):
    """Fused Eq. 1 belief update (previous round's receipts) + this
    round's Alg. 1/2 plan — one dispatch where the eager split costs
    two.  The update runs first on the same values ``observe`` would
    have passed, so the state sequence (and every plan drawn from it)
    is unchanged."""
    def update_plan(st, last, received, caches, online, rng, hints):
        st = core.update_after_round(st, last, received, fl_cfg)
        return st, _plan_body(st, caches, online, rng, hints, fl_cfg,
                              with_hints)
    return jax.jit(update_plan)


@functools.lru_cache(maxsize=8)
def _flude_update_jit(fl_cfg):
    def flude_update(st, plan, received):
        return core.update_after_round(st, plan, received, fl_cfg)
    return jax.jit(flude_update)


@register_policy("flude")
class FludePolicy(Policy):
    """The paper's policy: Beta-belief dependability selection (Alg. 1),
    adaptive staleness/quorum control (Alg. 2) and C3 cache resume, all
    planned on device in one fused jitted dispatch per round."""
    uses_cache = True
    # Alg. 2 line 3 caps X at clients_per_round before budget shrinking
    selects_at_most_clients_per_round = True

    def __init__(self, sim_cfg, fl_cfg, fleet=None, mesh=None):
        super().__init__(sim_cfg, fl_cfg, fleet, mesh=mesh)
        # §4.1 optional: bias exploration toward charged/stable devices.
        # The product stays host-side fp64 (bit-identical to the golden
        # runs); only the *placement* changes under a fleet mesh.
        self._hints = None
        if fleet is not None:
            self._hints = place_per_client(
                np.asarray(fleet.battery * fleet.stability, np.float32),
                mesh)
        self._plan_jit = _flude_plan_jit(fl_cfg, self._hints is not None)
        self._update_plan_jit = _flude_update_plan_jit(
            fl_cfg, self._hints is not None)
        self._update_jit = _flude_update_jit(fl_cfg)
        if self._hints is None:
            self._hints = place_per_client(
                np.zeros((fl_cfg.num_clients,), np.float32), mesh)

    def init_state(self) -> FludePolicyState:
        return FludePolicyState(core.init_state(self.fl_cfg), None, None)

    def plan(self, state, obs: RoundObservation, rng):
        # fold the parked previous-round receipts (Eq. 1) into this
        # round's plan dispatch — same update on the same values, one
        # dispatch instead of two
        if state.pending_received is not None:
            plan_fused = lambda st, caches, online, rng_, hints: \
                self._update_plan_jit(st, state.last,
                                      state.pending_received, caches,
                                      online, rng_, hints)
        else:
            plan_fused = lambda st, caches, online, rng_, hints: \
                (st, self._plan_jit(st, caches, online, rng_, hints))
        if obs.draw is not None:
            # device round path: the online mask, the belief update, the
            # plan AND the quorum clamp stay on device, and
            # RoundPlan.device runs structural checks only — planning is
            # a pure dispatch, so the pipelined engine loop never drains
            # the device queue here.
            st, p = plan_fused(state.core, obs.caches, obs.draw.online,
                               rng, self._hints)
            plan = RoundPlan.device(p.selected, p.distribute, p.resume,
                                    p.quorum)
            return FludePolicyState(st, p, None), plan
        # legacy host-RNG path: re-upload the numpy mask, validate on host
        st, p = plan_fused(state.core, obs.caches,
                           jnp.asarray(obs.online), rng, self._hints)
        quorum = float(p.quorum)    # already clamped inside the plan jit
        # masks stay jax arrays: the engine consumes them in place, and
        # the host path's np.asarray sees equal values
        plan = RoundPlan.create(p.selected, p.distribute, p.resume, quorum)
        return FludePolicyState(st, p, None), plan

    def observe(self, state, plan, report: RoundReport):
        # under correlated dynamics (markov/sessions/trace) the received
        # mask folds *correlated* outcomes into the Beta dependability
        # beliefs (Eq. 1) — the posterior tracks the realized process,
        # not an i.i.d. idealization; the update rule is unchanged.  The
        # mask is parked as-is (zero dispatches here) and the update
        # rides the next plan's jit (or the run-end flush below).
        return FludePolicyState(state.core, state.last,
                                jnp.asarray(report.received))

    def _flush(self, state) -> core.FludeState:
        """Apply the parked final-round update (run end: no next plan
        dispatch will fold it in)."""
        if state.pending_received is None:
            return state.core
        return self._update_jit(state.core, state.last,
                                state.pending_received)

    def history_extras(self, state):
        return {"part_count": np.asarray(self._flush(state).part_count)}


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------

@register_policy("random")
class RandomPolicy(Policy):
    """Vanilla FedAvg: uniform random selection, full distribution."""
    selects_at_most_clients_per_round = True

    def init_state(self) -> np.random.RandomState:
        return np.random.RandomState(self.sim_cfg.seed + 17)

    def plan(self, state, obs, rng):
        N = self.fl_cfg.num_clients
        sel = np.zeros(N, bool)
        idx = np.flatnonzero(obs.online)
        take = min(self.fl_cfg.clients_per_round, idx.size)
        sel[state.choice(idx, take, replace=False)] = True
        return state, RoundPlan.create(sel, sel, np.zeros(N, bool),
                                       float(take))


@dataclasses.dataclass(frozen=True)
class OortState:
    util: np.ndarray          # (N,) statistical utility (inf = unexplored)
    duration: np.ndarray      # (N,) last observed round duration
    eps: float
    rs: np.random.RandomState


@register_policy("oort")
class OortPolicy(Policy):
    """Oort [OSDI'21], simplified: statistical utility = loss·sqrt(n) with a
    system-speed penalty, ε-greedy exploration."""
    selects_at_most_clients_per_round = True

    def __init__(self, sim_cfg, fl_cfg, fleet=None, mesh=None):
        super().__init__(sim_cfg, fl_cfg, fleet, mesh=mesh)
        if fleet is None:
            raise ValueError("oort needs the fleet's speed profile")
        self.pref_duration = np.median(
            sim_cfg.local_steps / fleet.steps_per_sec)

    def init_state(self) -> OortState:
        N = self.fl_cfg.num_clients
        return OortState(np.full(N, np.inf), np.ones(N), 0.9,
                         np.random.RandomState(self.sim_cfg.seed + 29))

    def plan(self, state, obs, rng):
        N = self.fl_cfg.num_clients
        online = obs.online
        X = min(self.fl_cfg.clients_per_round, int(online.sum()))
        n_explore = int(round(state.eps * X))
        sel = np.zeros(N, bool)
        explored = np.isfinite(state.util)
        pool_new = np.flatnonzero(online & ~explored)
        take_new = min(n_explore, pool_new.size)
        if take_new:
            sel[state.rs.choice(pool_new, take_new, replace=False)] = True
        penal = np.where(state.duration > self.pref_duration,
                         (self.pref_duration / state.duration) ** 0.5, 1.0)
        score = np.where(online & explored & ~sel,
                         np.nan_to_num(state.util, posinf=0.0) * penal,
                         -np.inf)
        rest = X - sel.sum()
        if rest > 0:
            top = np.argsort(-score)[:rest]
            sel[top[score[top] > -np.inf]] = True
        new_state = dataclasses.replace(
            state, eps=max(state.eps * 0.98, 0.2))
        return new_state, RoundPlan.create(sel, sel, np.zeros(N, bool),
                                           float(sel.sum()))

    def observe(self, state, plan, report):
        upd = np.asarray(plan.selected) & report.received
        util = np.where(upd, report.losses * np.sqrt(
            self.sim_cfg.batch_size * self.sim_cfg.local_steps), state.util)
        duration = np.where(upd, report.durations, state.duration)
        return dataclasses.replace(state, util=util, duration=duration)


@register_policy("safa")
class SafaPolicy(Policy):
    """SAFA [IEEE TC'20], simplified semi-async: crashed/straggling devices
    keep local progress (lag-tolerant cache) and are force-synced only when
    their version lag exceeds τ.  Rounds close on SAFA's synchronization
    quota (a fraction of the selected set), not on the last arrival —
    that is what makes it SEMI-async."""
    uses_cache = True
    quota = 0.75
    selects_at_most_clients_per_round = True

    def __init__(self, sim_cfg, fl_cfg, fleet=None, mesh=None,
                 tau: int = 5):
        super().__init__(sim_cfg, fl_cfg, fleet, mesh=mesh)
        self.tau = tau

    def init_state(self) -> np.random.RandomState:
        return np.random.RandomState(self.sim_cfg.seed + 43)

    def plan(self, state, obs, rng):
        N = self.fl_cfg.num_clients
        sel = np.zeros(N, bool)
        idx = np.flatnonzero(obs.online)
        take = min(self.fl_cfg.clients_per_round, idx.size)
        sel[state.choice(idx, take, replace=False)] = True
        stamp = np.asarray(obs.caches.round_stamp)
        lag = np.where(stamp >= 0, obs.rnd - stamp, BIG)
        resume = sel & (lag <= self.tau)
        # quota of a small selected set can floor to 0, which would
        # idle-wait the full deadline every round — any selected set
        # needs a quorum of at least one upload
        quorum = float(np.floor(sel.sum() * self.quota))
        if take > 0:
            quorum = max(quorum, 1.0)
        return state, RoundPlan.create(sel, sel & ~resume, resume, quorum)


@register_policy("fedsea")
class FedSeaPolicy(Policy):
    """FedSEA [SenSys'22], simplified: balance completion times by scaling
    local steps with device speed; deadline-based aggregation."""
    waits_for_stragglers = False
    selects_at_most_clients_per_round = True

    def __init__(self, sim_cfg, fl_cfg, fleet=None, mesh=None):
        super().__init__(sim_cfg, fl_cfg, fleet, mesh=mesh)
        if fleet is None:
            raise ValueError("fedsea needs the fleet's speed profile")
        rel = fleet.steps_per_sec / fleet.steps_per_sec.max()
        self.steps = np.clip(
            np.round(sim_cfg.local_steps * rel), 1,
            sim_cfg.local_steps).astype(np.int32)

    def init_state(self) -> np.random.RandomState:
        return np.random.RandomState(self.sim_cfg.seed + 57)

    def plan(self, state, obs, rng):
        N = self.fl_cfg.num_clients
        sel = np.zeros(N, bool)
        idx = np.flatnonzero(obs.online)
        take = min(self.fl_cfg.clients_per_round, idx.size)
        sel[state.choice(idx, take, replace=False)] = True
        return state, RoundPlan.create(sel, sel, np.zeros(N, bool),
                                       float(sel.sum()),
                                       steps_override=self.steps)


@register_policy("mifa")
class MifaPolicy(Policy):
    """MIFA [NeurIPS'21, arXiv 2106.04159], adapted: memorized-update FL
    under arbitrary device unavailability.

    MIFA's server keeps every client's most recent update and aggregates
    *all* of them each round, stale or not, at full weight — that
    unbiasedness under unavailability is the whole point.  In this engine
    the memory is realized through the C3 cache machinery: every online
    device trains (no subsampling), interrupted devices keep their local
    progress cached and *always* resume it at the next opportunity, and the
    policy cancels the server's staleness discount through ``agg_weights``
    (``(1+s)^{+d}`` against the engine's ``(1+s)^{-d}``) so memorized
    stale-base updates aggregate undiscounted — the memorized-update
    stress test for the aggregation-weight machinery.
    """
    uses_cache = True
    waits_for_stragglers = False

    def init_state(self):
        return None

    def plan(self, state, obs, rng):
        sel = obs.online.copy()
        stamp = np.asarray(obs.caches.round_stamp)
        resume = sel & (stamp >= 0)
        # undo the engine's staleness discount on resumed (memorized) bases
        stale = np.where(resume, np.maximum(obs.rnd - stamp, 0), 0)
        w = np.power(1.0 + stale,
                     self.fl_cfg.staleness_discount).astype(np.float32)
        return state, RoundPlan.create(sel, sel & ~resume, resume,
                                       float(sel.sum()), agg_weights=w)


@register_policy("asyncfeded")
class AsyncFedEdPolicy(Policy):
    """AsyncFedED [2022], simplified: every online device trains; arrivals
    are aggregated with staleness-adaptive weights (euclidean-distance
    surrogate = version lag)."""
    waits_for_stragglers = False

    def init_state(self) -> np.ndarray:
        return np.zeros(self.fl_cfg.num_clients, np.int32)   # last sync rnd

    def plan(self, state, obs, rng):
        sel = obs.online.copy()
        lag = obs.rnd - state
        w = 1.0 / (1.0 + np.maximum(lag, 0))
        return state, RoundPlan.create(sel, sel, np.zeros_like(sel),
                                       float(sel.sum()), agg_weights=w)

    def observe(self, state, plan, report):
        return np.where(report.received, report.rnd, state)
