"""FleetEngine: the device-resident FL round loop behind the typed API.

The engine owns the vectorized local trainer, the fused jitted server
round step (weights + packed aggregation + C3 cache bookkeeping) and the
fleet simulator; policies are pure ``plan``/``observe`` transitions over
typed ``RoundPlan``/``RoundReport`` messages (see ``repro.fl.api``).

Global params and client caches stay device-resident across rounds.  On
the device-dynamics round path the round *close* is device-resident too:
a jitted quorum cut (``core.make_round_cut``) turns the (N,) finish
times into the cut, the billed duration and the receive mask without a
host sync, History bookkeeping is deferred through a ``_RoundLedger``
(read back at eval boundaries and run end), and
``FLConfig.pipeline_depth`` > 1 lets the host dispatch round k+1's fused
trainer + server step while round k still executes — trajectories are
bit-identical at every depth.  The legacy host-RNG loop
(``bernoulli_host``) keeps the historical numpy close verbatim.

With ``FLConfig.mesh_shape`` set, the fleet lives *sharded* over a
``("clients",)`` mesh axis: client training data, the stacked client
pytree (caches + trainer outputs), the packed (C, D) aggregation buffer
and every (N,) per-client array are placed with ``jax.device_put`` at
engine construction and stay sharded across rounds; aggregation runs as
per-shard partial weighted sums + one fp32 psum (shard_map).  The global
model is replicated.  ``FLConfig.donate_buffers`` additionally donates
the dead round inputs on the jitted trainer / server-step calls so XLA
aliases them into the outputs and steady-state rounds allocate nothing
new.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import core
from repro.configs.base import FLConfig
from repro.data.synthetic import FederatedClassification
from repro.fl import models as FLM
from repro.fl.api import (Policy, RoundObservation, RoundPlan, RoundReport,
                          cohort_index, cohort_overflow, make_policy)
from repro.fl import policies as _builtin_policies  # noqa: F401  (registers)
from repro.fl.simulator import Fleet, SimConfig, place_per_client
from repro.fleet import (get_dynamics, make_adversary,  # registers processes
                         make_dynamics)
from repro.launch.mesh import make_fleet_mesh
from repro import obs
from repro.sharding import partitioning as SP

BIG = 1 << 20


# ---------------------------------------------------------------------------
# Vectorized local trainer
# ---------------------------------------------------------------------------

def make_trainer(sim_cfg: SimConfig, data: FederatedClassification,
                 mesh=None, donate: bool = False, dynamics_features=None,
                 cohort_size: Optional[int] = None,
                 external_cache_params: bool = False,
                 model: Optional["FLM.FLModel"] = None):
    """Build the jitted all-fleet local trainer.

    ``model`` (a ``repro.fl.models.FLModel``, default the ``"mlp"``
    classifier) supplies the per-client loss and gradient.  Every variant
    takes the model's frozen leaves as its last argument ``frozen``
    (default: none): one copy the whole cohort shares, a jit argument
    (never closed over, so it is not compiled into the executable, and
    never donated).  Only the trainable leaves are trained, snapshotted
    into caches and returned.

    ``mesh``: optional ``("clients",)`` fleet mesh — the per-client
    training set (N, n, d)/(N, n) is placed sharded over clients so each
    device trains only its own shard of the fleet (the computation is
    embarrassingly parallel; the only broadcast input is the global
    model).  ``donate=True`` donates the per-round (N,) step-count carry
    (steps_needed) so its buffer is recycled into the (N,)-shaped
    cached-steps output; the other big inputs — global model and caches —
    are still live after the call (the server step reads them) and must
    not be donated here.

    ``dynamics_features``: a ``repro.fleet.FleetFeatures`` switches the
    build to the device-resident dynamics variant: the round's workload
    (steps from cache progress), exposure-scaled failures + interruption
    points (from the ``FleetDraw`` variates) and the per-device timing
    model are fused *into* the jitted trainer, so the whole round body is
    one dispatch over device-resident inputs — nothing is drawn on the
    host and nothing (N,)-sized is uploaded per round.  No argument is
    donated on this variant (the draw is also exposed to policies via
    ``RoundObservation`` and must stay live).

    ``cohort_size``: static X (dynamics variant only) switches to the
    compact-cohort round body: the cohort index is derived on device
    from the plan's selection mask, the clients' data / caches / draw /
    plan arrays are gathered into dense (X, ...) blocks, and the
    vmap+scan runs over X rows instead of N — round FLOPs track the
    cohort, not the fleet.  Returns the (X,) blocks the compact cut and
    server step consume, plus scattered (N,) report views (losses /
    fail / finish times) for policies, the cohort index, and a device
    overflow flag (``|selected| > X`` — the engine defers it through
    the round ledger).  Everything happens inside the one jitted
    dispatch: compaction adds no per-round host transfer.

    ``external_cache_params``: the ``cache_offload`` trainer variant
    (requires ``cohort_size``).  ``caches`` then carries metadata only
    (empty params pytree) and the cohort's (X, ...) cache-params block
    arrives as an explicit argument — fetched from the host store by
    the engine's cache stream — together with the precomputed cohort
    index (the engine derives it in its own small jit so the host can
    start the fetch as soon as the selection mask is dispatched).  The
    round body is otherwise identical, so outputs are bit-identical to
    the resident cohort variant fed the same rows.
    """
    x_all = jnp.asarray(data.x)            # (N, n, d)
    y_all = jnp.asarray(data.y)            # (N, n)
    if mesh is not None:
        # the engine only builds a mesh that divides the fleet evenly
        x_all = jax.device_put(x_all, SP.fleet_sharding(mesh, x_all.ndim))
        y_all = jax.device_put(y_all, SP.fleet_sharding(mesh, y_all.ndim))
    n = x_all.shape[1]
    b = min(sim_cfg.batch_size, n)
    lr = sim_cfg.lr
    max_steps = sim_cfg.local_steps

    grad_fn = (model or FLM.get_model("mlp")).value_and_grad
    donate_argnums = (3,) if donate and dynamics_features is None else ()
    if cohort_size is not None and dynamics_features is None:
        raise ValueError("cohort_size requires the dynamics trainer "
                         "variant (pass dynamics_features)")
    if external_cache_params and cohort_size is None:
        raise ValueError("external_cache_params requires the compact "
                         "cohort trainer variant (pass cohort_size)")

    def local_scan(x_arr, y_arr, start_params, steps_needed, stop_step,
                   cache_every, frozen):
        """The shared masked local-training scan body.  ``x_arr``/
        ``y_arr`` carry the client axis — the full (N, n, d) fleet or a
        gathered (X, n, d) cohort block; the per-client math is
        elementwise over that axis either way."""
        zero_cache = start_params
        loss0 = jnp.zeros((x_arr.shape[0],), jnp.float32)

        def step_fn(carry, j):
            params, cache, cached_steps, loss_sum = carry
            idx = (j * b + jnp.arange(b)) % n
            xb = x_arr[:, idx]
            yb = y_arr[:, idx]
            loss, grads = grad_fn(params, frozen, xb, yb)
            active = (j < steps_needed) & (j < stop_step)

            def upd(p, g):
                m = active.reshape((-1,) + (1,) * (p.ndim - 1))
                return jnp.where(m, p - lr * g, p)

            params = jax.tree.map(upd, params, grads)
            do_cache = active & (((j + 1) % jnp.maximum(cache_every, 1))
                                 == 0)

            def cupd(c, p):
                m = do_cache.reshape((-1,) + (1,) * (p.ndim - 1))
                return jnp.where(m, p, c)

            cache = jax.tree.map(cupd, cache, params)
            cached_steps = jnp.where(do_cache, j + 1, cached_steps)
            loss_sum = loss_sum + jnp.where(active, loss, 0.0)
            return (params, cache, cached_steps, loss_sum), None

        init = (start_params, zero_cache,
                jnp.zeros((x_arr.shape[0],), jnp.int32), loss0)
        (params, cache, cached_steps, loss_sum), _ = jax.lax.scan(
            step_fn, init, jnp.arange(max_steps))
        # normalize by the steps that actually *ran*: the scan is
        # max_steps long, so a larger request trains (and accumulates
        # loss over) max_steps at most
        done = jnp.minimum(jnp.minimum(steps_needed, stop_step), max_steps)
        mean_loss = loss_sum / jnp.maximum(done, 1)
        return params, cache, cached_steps, mean_loss

    if dynamics_features is None:
        @functools.partial(jax.jit, donate_argnums=donate_argnums)
        def train_all(global_params, caches, resume, steps_needed,
                      stop_step, cache_every, frozen=None):
            """All-fleet masked local training (incl. fused resume
            selection).

            global_params: unstacked global model; each client starts from
                           it unless ``resume`` picks its cached state.
            caches:       core.ClientCaches (stacked (N, ...) params).
            resume:       (N,) bool — train from local cache (C3/C4).
            steps_needed: (N,) steps each device must run (0 = idle).
            stop_step:    (N,) interruption step (>= steps_needed: no
                          failure).
            cache_every:  (N,) cache interval in steps (C3 adaptive).
            Returns (final_params, cache_params, cached_steps, mean_loss).
            """
            start_params = core.resume_params(caches, global_params, resume)
            return local_scan(x_all, y_all, start_params, steps_needed,
                              stop_step, cache_every, frozen)

        return train_all

    feats = dynamics_features
    model_mb = sim_cfg.model_mb

    def round_body(x_arr, y_arr, steps_per_sec, global_params, caches,
                   draw, selected, distribute, resume, base_steps,
                   cache_every, frozen):
        """Workload + failures + training + timing over one client axis
        (the full fleet, or a gathered cohort block — every input is
        aligned along dim 0)."""
        # clamp to the scan length: an oversized steps_override would
        # otherwise charge un-run steps in the timing model below
        base_steps = jnp.minimum(base_steps, max_steps)
        prior = jnp.round(caches.progress * max_steps).astype(jnp.int32)
        steps_needed = jnp.where(resume, jnp.maximum(base_steps - prior, 1),
                                 base_steps)
        steps_needed = jnp.where(selected, steps_needed, 0) \
            .astype(jnp.int32)
        fail = draw.failure_mask(steps_needed / max(max_steps, 1)) \
            & selected
        stop = jnp.where(fail, draw.interruption_step(steps_needed), BIG)
        start_params = core.resume_params(caches, global_params, resume)
        params, cache, cached_steps, mean_loss = local_scan(
            x_arr, y_arr, start_params, steps_needed, stop, cache_every,
            frozen)
        # timing model (Algorithm 2 lines 13–16) on the round's bandwidth
        success = selected & ~fail & (steps_needed > 0)
        completed = jnp.minimum(steps_needed, stop)
        comm = model_mb * 8.0 / draw.bandwidth
        t = jnp.where(distribute, comm, 0.0) \
            + completed / steps_per_sec \
            + jnp.where(success, comm, 0.0)
        times = jnp.where(success, t, jnp.inf)
        return (params, cache, cached_steps, mean_loss, steps_needed, fail,
                success, times)

    if cohort_size is None:
        @jax.jit
        def train_all_dyn(global_params, caches, draw, selected,
                          distribute, resume, base_steps, cache_every,
                          frozen=None):
            """Dynamics round body: workload + failures + training +
            timing.

            draw:       repro.fleet.FleetDraw for this round (device
                        arrays).
            selected/distribute/resume: (N,) bool plan masks.
            base_steps: (N,) int planned steps before resume credit.
            Returns (final_params, cache_params, cached_steps, mean_loss,
            steps_needed, fail, success, times) — times in simulated
            seconds, inf where the device never uploads.
            """
            return round_body(x_all, y_all, feats.steps_per_sec,
                              global_params, caches, draw, selected,
                              distribute, resume, base_steps, cache_every,
                              frozen)

        return train_all_dyn

    X = int(cohort_size)
    N = x_all.shape[0]

    def cohort_round(idx, cache_params_x, global_params, caches, draw,
                     selected, distribute, resume, base_steps,
                     cache_every, frozen):
        """Shared gather → (X, ...) round body → scatter given the cohort
        index.  ``cache_params_x`` is None on the resident path (the
        cohort's cache slots are gathered from the (N, D) pytree) or the
        externally-fetched (X, ...) block on the offload path — every
        other op is identical, which is what keeps the two variants
        bit-identical row for row."""
        def take(a, fill):
            return jnp.take(a, idx, axis=0, mode="fill", fill_value=fill)

        sel_x = take(selected, False)
        dist_x = take(distribute, False)
        res_x = take(resume, False)
        base_x = take(base_steps, 0)
        ce_x = take(cache_every, 1)
        sps_x = take(feats.steps_per_sec, 1.0)
        draw_x = draw.take(idx)
        if cache_params_x is None:
            caches_x = core.gather_caches(caches, idx)
        else:
            caches_x = core.ClientCaches(cache_params_x,
                                         take(caches.progress, 0.0),
                                         take(caches.round_stamp, -1))
        x_x = jnp.take(x_all, idx, axis=0, mode="fill", fill_value=0)
        y_x = jnp.take(y_all, idx, axis=0, mode="fill", fill_value=0)
        (x_x, y_x, caches_x, draw_x, sel_x, dist_x, res_x, base_x, ce_x,
         sps_x) = SP.cohort_constraint(
            (x_x, y_x, caches_x, draw_x, sel_x, dist_x, res_x, base_x,
             ce_x, sps_x), mesh, X)

        (params, cache, cached_steps, mean_loss, steps_needed, fail,
         success, times) = round_body(
            x_x, y_x, sps_x, global_params, caches_x, draw_x, sel_x,
            dist_x, res_x, base_x, ce_x, frozen)

        # (N,) report views: scatter the cohort rows, fill the rest with
        # exactly what the full scan computes for idle clients (loss 0,
        # no failure, inf finish time); sentinel rows drop
        losses_n = jnp.zeros((N,), mean_loss.dtype) \
            .at[idx].set(mean_loss, mode="drop")
        fail_n = jnp.zeros((N,), bool).at[idx].set(fail, mode="drop")
        times_n = jnp.full((N,), jnp.inf, times.dtype) \
            .at[idx].set(times, mode="drop")
        losses_n, fail_n, times_n = SP.cohort_scatter_constraint(
            (losses_n, fail_n, times_n), mesh, N)
        return (params, cache, cached_steps, mean_loss, steps_needed,
                fail, success, times, losses_n, fail_n, times_n)

    if external_cache_params:
        @jax.jit
        def train_cohort_dyn_offload(global_params, caches,
                                     cache_params_x, idx, draw, selected,
                                     distribute, resume, base_steps,
                                     cache_every, frozen=None):
            """Offload cohort round body: like ``train_cohort_dyn`` but
            the cohort index arrives precomputed (the engine's idx jit —
            same ``cohort_index`` values) and the cohort's cache params
            arrive as the host-store fetch; ``caches`` carries metadata
            only.  Returns the 11-tuple without ``idx``/``overflow``
            (the engine already holds both)."""
            idx = SP.cohort_constraint(idx, mesh, X)
            return cohort_round(idx, cache_params_x, global_params,
                                caches, draw, selected, distribute,
                                resume, base_steps, cache_every, frozen)

        return train_cohort_dyn_offload

    @jax.jit
    def train_cohort_dyn(global_params, caches, draw, selected,
                         distribute, resume, base_steps, cache_every,
                         frozen=None):
        """Compact-cohort dynamics round body (see the factory
        docstring): gather → (X, ...) round body → scatter, one dispatch.

        Inputs are the same (N,)-sized round arrays as the full-scan
        variant; the cohort index is derived *inside* the jit.  Returns
        ``(final_params_x, cache_params_x, cached_steps_x, mean_loss_x,
        steps_needed_x, fail_x, success_x, times_x, idx, overflow,
        losses_n, fail_n, times_n)`` — the ``_x`` blocks are (X,)-leading
        cohort arrays; ``losses_n``/``fail_n``/``times_n`` are the (N,)
        report views policies consume (idle clients read the same
        zero-loss / no-fail / inf-time values the full scan computes for
        them).
        """
        idx = cohort_index(selected, X)
        idx = SP.cohort_constraint(idx, mesh, X)
        overflow = cohort_overflow(selected, X)
        outs = cohort_round(idx, None, global_params, caches, draw,
                            selected, distribute, resume, base_steps,
                            cache_every, frozen)
        overflow, = SP.replicated_constraint((overflow,), mesh)
        return outs[:8] + (idx, overflow) + outs[8:]

    return train_cohort_dyn


# ---------------------------------------------------------------------------
# Round history
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class History:
    acc: List[float] = dataclasses.field(default_factory=list)
    comm_mb: List[float] = dataclasses.field(default_factory=list)   # cum.
    wall_clock: List[float] = dataclasses.field(default_factory=list)
    received: List[int] = dataclasses.field(default_factory=list)
    selected: List[int] = dataclasses.field(default_factory=list)
    # eval_mask[t] is False when acc[t] is a carried-forward stale value
    # (eval_every > 1 skipped the measurement that round)
    eval_mask: List[bool] = dataclasses.field(default_factory=list)
    part_count: Optional[np.ndarray] = None
    per_class_acc: Optional[np.ndarray] = None
    per_client_acc: Optional[np.ndarray] = None
    final_params: Any = None
    # per-round device telemetry (FLConfig.telemetry / run(telemetry=..)):
    # metric column -> list over rounds; None when telemetry is off
    metrics: Optional[dict] = None

    # optional ndarray attributes that round-trip through to_json (trust
    # is attached dynamically by stateful robust rules)
    _ARRAY_EXTRAS = ("part_count", "per_class_acc", "per_client_acc",
                     "trust")

    def to_json(self) -> dict:
        """JSON-serializable trajectory dict (the golden-file format);
        ``final_params`` is deliberately excluded."""
        d = {"acc": [float(a) for a in self.acc],
             "comm_mb": [float(c) for c in self.comm_mb],
             "wall_clock": [float(t) for t in self.wall_clock],
             "received": [int(r) for r in self.received],
             "selected": [int(s) for s in self.selected],
             "eval_mask": [bool(m) for m in self.eval_mask]}
        for name in self._ARRAY_EXTRAS:
            v = getattr(self, name, None)
            if v is not None:
                d[name] = np.asarray(v).tolist()
        if self.metrics is not None:
            d["metrics"] = {k: list(v) for k, v in self.metrics.items()}
        return d

    @classmethod
    def from_json(cls, d: dict) -> "History":
        """Inverse of ``to_json``; tolerates pre-refactor golden dicts
        (no ``eval_mask``/extras — the empty mask reads as all-True,
        matching ``_evaluated``)."""
        h = cls(acc=[float(a) for a in d.get("acc", ())],
                comm_mb=[float(c) for c in d.get("comm_mb", ())],
                wall_clock=[float(t) for t in d.get("wall_clock", ())],
                received=[int(r) for r in d.get("received", ())],
                selected=[int(s) for s in d.get("selected", ())],
                eval_mask=[bool(m) for m in d.get("eval_mask", ())])
        for name in cls._ARRAY_EXTRAS:
            if d.get(name) is not None:
                setattr(h, name, np.asarray(d[name]))
        if d.get("metrics") is not None:
            h.metrics = {k: list(v) for k, v in d["metrics"].items()}
        return h

    def _evaluated(self):
        mask = self.eval_mask or [True] * len(self.acc)
        for t, c, a, m in zip(self.wall_clock, self.comm_mb, self.acc,
                              mask):
            if m:
                yield t, c, a

    def time_to_accuracy(self, target: float) -> float:
        for t, _, a in self._evaluated():
            if a >= target:
                return t
        return float("inf")

    def comm_to_accuracy(self, target: float) -> float:
        for _, c, a in self._evaluated():
            if a >= target:
                return c
        return float("inf")


def _metric_py(v):
    """One resolved metric value -> plain python (scalar or list)."""
    a = np.asarray(v)
    if a.ndim:
        return a.tolist()
    if a.dtype == bool or np.issubdtype(a.dtype, np.integer):
        return int(a)
    return float(a)


class _RoundLedger:
    """Deferred History bookkeeping for the pipelined device round loop.

    Each round the loop *dispatches* the device scalars one History row
    needs — billed duration, received/download/selected counts and (at
    eval boundaries) the round's test accuracy — and pushes the handles
    here.  ``resolve`` reads rows back oldest-first; the loop calls it
    with ``keep = pipeline_depth - 1`` so at most that many rounds of
    bookkeeping stay in flight, and with ``keep=0`` at run end (and every
    round under a ``time_budget``, whose check needs ``cum_time``).

    The f64 accumulation of ``cum_comm``/``cum_time`` happens here on the
    host at resolve time, over per-round values that are exact float32 —
    deadline-capped rounds arrive as a ``capped`` flag and bill the exact
    (float64) ``round_deadline`` — so trajectories are bit-identical at
    every depth, and identical to the old eager ``_book_round`` loop.
    """

    def __init__(self, hist: History, model_mb: float,
                 round_deadline: float, progress: Optional[Callable],
                 n_rounds: int, cohort_info: Optional[tuple] = None,
                 telemetry=None, tracer=None):
        self.hist = hist
        self.model_mb = model_mb
        self.round_deadline = round_deadline
        self.progress = progress
        self.n_rounds = n_rounds
        self.cohort_info = cohort_info    # (policy_name, cohort_size)
        self.telemetry = telemetry        # repro.obs.Telemetry | None
        self.tracer = tracer if tracer is not None else obs.NULL_TRACER
        self.pending: List[tuple] = []
        self.cum_comm = 0.0
        self.cum_time = 0.0
        self.acc = float("nan")

    def push(self, rnd, evaluated, duration, capped, received, downloads,
             selected, acc, overflow=None, metrics=None):
        """Queue one round's device-scalar bookkeeping handles.

        ``overflow`` (compact-cohort rounds) is the device flag for
        ``|selected| > cohort_size``: like every other handle it is read
        back at resolve time, so under ``pipeline_depth`` > 1 a cohort
        overflow surfaces up to depth-1 rounds after it happened — the
        documented cost of keeping the check off the per-round hot path.

        ``metrics`` (telemetry on) is the fused metrics dispatch's dict
        of device scalars/vectors: it joins the same deferred read, so
        telemetry adds handles to an existing host sync, never a new
        one.
        """
        self.pending.append((rnd, evaluated, duration, capped, received,
                             downloads, selected, acc, overflow,
                             metrics))

    def resolve(self, keep: int = 0):
        """Read back (host-sync) all but the newest ``keep`` rounds."""
        while len(self.pending) > keep:
            (rnd, evaluated, duration, capped, received, downloads,
             selected, acc_dev, overflow, metrics) = self.pending.pop(0)
            with self.tracer.span("ledger_resolve", round=rnd):
                (duration, capped, received, downloads, selected,
                 overflow, metrics) = jax.device_get(
                    (duration, capped, received, downloads, selected,
                     overflow, metrics))
            if overflow is not None and bool(overflow):
                name, x = self.cohort_info or ("<unknown>", "?")
                raise RuntimeError(
                    f"cohort overflow in round {rnd}: policy {name!r} "
                    f"selected {int(selected)} clients but "
                    f"FLConfig.cohort_size={x} — the compact round "
                    f"trained a truncated cohort.  Raise cohort_size "
                    f"(or set it to None for the full scan).")
            self.cum_comm += (int(downloads) + int(received)) \
                * self.model_mb
            billed = self.round_deadline if bool(capped) \
                else float(duration)
            self.cum_time += billed
            if evaluated:
                # the eval scalar is the one extra readback an eval
                # boundary costs — spanned so Perfetto shows it next to
                # ledger_resolve like every other host-sync seam
                with self.tracer.span("eval_readback", round=rnd):
                    self.acc = float(jax.device_get(acc_dev))
            hist = self.hist
            hist.acc.append(self.acc)
            hist.eval_mask.append(evaluated)
            hist.comm_mb.append(self.cum_comm)
            hist.wall_clock.append(self.cum_time)
            hist.received.append(int(received))
            hist.selected.append(int(selected))
            if metrics is not None:
                vals = {k: _metric_py(v) for k, v in metrics.items()}
                if hist.metrics is None:
                    hist.metrics = {}
                for k, v in vals.items():
                    hist.metrics.setdefault(k, []).append(v)
                if self.telemetry is not None:
                    self.telemetry.record_round({
                        "round": rnd, "evaluated": evaluated,
                        "acc": None if self.acc != self.acc else self.acc,
                        "duration": billed, "comm_mb": self.cum_comm,
                        "wall_clock": self.cum_time,
                        "received": int(received),
                        "downloads": int(downloads),
                        "selected": int(selected), **vals})
            if self.progress and (rnd % 10 == 0
                                  or rnd == self.n_rounds - 1):
                self.progress(rnd, self.acc, self.cum_comm, self.cum_time)


# ---------------------------------------------------------------------------
# FleetEngine
# ---------------------------------------------------------------------------

class FleetEngine:
    """Owns trainer + fused server step + fleet; runs policies by name.

    The fleet trainer (legacy or dynamics variant, per
    ``FLConfig.dynamics``) is jitted on first use and reused across
    ``run`` calls (different policies, same task) — the multi-policy
    comparison loop of the paper's Table 1.

        engine = FleetEngine(data, sim_cfg, fl_cfg)
        hist = engine.run("flude")                      # sim_cfg.rounds
        hist = engine.run("random", time_budget=3600.0)

    A fleet passed to the constructor is reused (and its RNG advances
    across runs); otherwise each run draws a fresh ``Fleet(sim_cfg)`` so
    fixed seeds reproduce.
    """

    def __init__(self, data: FederatedClassification, sim_cfg: SimConfig,
                 fl_cfg: FLConfig, fleet: Optional[Fleet] = None):
        # adversarial fleet (repro.fleet.adversary): resolve the attack
        # model up front — the malicious mask is drawn once (determin-
        # istic in the sim seed), label poisoning rewrites the training
        # set before the trainer ever sees it, and model poisoning rides
        # inside the jitted server step via ``adversary_scale``.  Rounds
        # add zero host syncs either way.
        self._adversary = None
        self._adv_scale = None
        self._malicious_np = None
        if fl_cfg.adversary is not None:
            self._adversary = make_adversary(fl_cfg.adversary,
                                             fl_cfg.adversary_params)
            self._malicious_np = self._adversary.malicious_mask(
                fl_cfg.num_clients, sim_cfg.seed)
            self._adv_scale = self._adversary.delta_scale
            if self._adversary.flips_labels:
                data = self._adversary.corrupt_data(data,
                                                    self._malicious_np)
        # robust aggregation rule (repro.core.agg_rules): "mean" keeps
        # the historical direct path (rule None); a stateful rule adds a
        # device-resident (N,) state vector threaded through rounds
        self._agg_rule = None
        if fl_cfg.agg_rule not in (None, "mean"):
            self._agg_rule = core.make_agg_rule(fl_cfg.agg_rule,
                                                fl_cfg.agg_rule_params)
        self._agg_stateful = (self._agg_rule is not None
                              and self._agg_rule.stateful)
        self.data = data
        self.sim_cfg = sim_cfg
        self.fl_cfg = fl_cfg
        self._fleet = fleet
        self.mesh = self._build_mesh(fl_cfg)
        self.donate = bool(fl_cfg.donate_buffers)
        self.pipeline_depth = int(fl_cfg.pipeline_depth)
        if self.pipeline_depth < 1:
            raise ValueError(f"FLConfig.pipeline_depth must be >= 1, got "
                             f"{fl_cfg.pipeline_depth}")
        self.cohort = fl_cfg.cohort_size
        self.offload = fl_cfg.cache_offload
        if self.cohort is not None \
                and get_dynamics(fl_cfg.dynamics).host_side:
            raise ValueError(
                f"FLConfig.cohort_size requires a device dynamics "
                f"process, but {fl_cfg.dynamics!r} is host-side — the "
                f"legacy numpy round loop has no compact path (pick a "
                f"device process, e.g. 'bernoulli', or set "
                f"cohort_size=None)")
        self._trainer = None      # legacy trainer, built on first host run
        # the model clients train (repro.fl.models): its trainable leaves
        # are the global model every round moves; its frozen leaves (an
        # LLM trunk) are placed once and passed to the trainer and eval
        self.model = FLM.get_model(fl_cfg.model)
        self._acc_fn = jax.jit(self.model.accuracy)
        self._server_steps = {}
        self._last_caches = None  # previous run's fleet caches (recycled)
        self._cache_reset = None  # donated in-place zero-fill, built lazily
        template, frozen = self.model.init(sim_cfg.seed, data, sim_cfg)
        # place everything the rounds touch once, at construction: the
        # global model, frozen leaves and test set replicated, per-client
        # arrays sharded
        if self.mesh is not None:
            template = jax.device_put(
                template, jax.tree.map(
                    lambda _: SP.replicated_sharding(self.mesh), template))
        tracer = obs.Tracer() if fl_cfg.telemetry is not None \
            else obs.NULL_TRACER
        with tracer.span("trunk_put"):
            if self.mesh is not None:
                frozen = jax.device_put(frozen, jax.tree.map(
                    lambda _: SP.replicated_sharding(self.mesh), frozen))
            self._frozen = jax.block_until_ready(frozen)
        self._template = template
        self._test_x, self._test_y, self._n_samples = self._place_eval()
        # device-resident fleet dynamics (repro.fleet): jitted step /
        # fused round trainer, memoized per (process, params); per-run
        # (N,) constants are placed once and reused so steady-state
        # rounds never re-upload anything
        get_dynamics(fl_cfg.dynamics)          # fail fast on unknown names
        self._dyn_cache = {}
        self._round_consts = {}
        self._cut_fns = {}                     # jitted round cut per trait
        # the malicious mask is per-run-invariant: placed once, reused
        self._malicious = None if self._adv_scale is None else \
            self._put1(self._malicious_np)
        # host-offloaded C3 cache store (cache_offload="host"/"discard"):
        # the (N, D) cache params live in a sparse host store; the device
        # keeps (N,) metadata plus the round's (X, D) cohort block, and
        # the stream double-buffers the fetch/write-back copies
        self.cache_store = None
        self._cache_stream = None
        self._idx_fn = None
        self._expire_fn = None
        self._zeros_x = None
        # per-engine transfer counters (strictly per-engine — the old
        # module-global aggregate is gone)
        self._transfer_stats = core.TransferStats()
        if self.offload is not None:
            bound = fl_cfg.cache_staleness_bound \
                if self.offload == "discard" else None
            self.cache_store = core.HostCacheStore(
                self._template, fl_cfg.num_clients,
                staleness_bound=bound, stats=self._transfer_stats)
            self._cache_stream = core.CohortCacheStream(
                self.cache_store, mesh=self.mesh,
                cohort_size=self.cohort)
        # telemetry (repro.obs): fused metrics dispatches are memoized
        # per (level, path); the run-scoped tracer is NULL when off, so
        # instrumented seams cost one attribute lookup on default runs
        self._metrics_fns = {}
        self._load_fn = None      # expert-load counter, built lazily
        self._tracer = obs.NULL_TRACER
        # debug_checks sanitizer mode (repro.analysis.runtime): checkify
        # round guard + recompilation detector, both built lazily —
        # default runs never import the analysis package
        self.debug_checks = bool(fl_cfg.debug_checks)
        self._round_guard = None
        self._recomp_detector = None

    def _build_mesh(self, fl_cfg: FLConfig):
        if fl_cfg.mesh_shape is None:
            return None
        shape = tuple(fl_cfg.mesh_shape)
        if len(shape) != 1:
            raise ValueError(f"FLConfig.mesh_shape must be 1-D (clients "
                             f"axis), got {shape}")
        if shape[0] == 1:
            return None          # single device: today's exact round path
        if fl_cfg.num_clients % shape[0] != 0:
            raise ValueError(
                f"mesh_shape {shape} does not divide the "
                f"{fl_cfg.num_clients}-client fleet — shard_map needs an "
                f"even client split")
        return make_fleet_mesh(shape[0])

    def _place_eval(self):
        test_x = jnp.asarray(self.data.test_x)
        test_y = jnp.asarray(self.data.test_y)
        n_samples = jnp.full((self.fl_cfg.num_clients,),
                             self.data.x.shape[1], jnp.float32)
        if self.mesh is not None:
            rep = SP.replicated_sharding(self.mesh)
            test_x = jax.device_put(test_x, rep)
            test_y = jax.device_put(test_y, rep)
            n_samples = jax.device_put(n_samples,
                                       SP.fleet_sharding(self.mesh))
        return test_x, test_y, n_samples

    @property
    def trainer(self):
        """The legacy (host-draw) jitted trainer, built lazily: an engine
        configured with a device dynamics process never calls it, and the
        dynamics trainer places its own copy of the training set — eager
        construction would keep two full device copies of the data."""
        if self._trainer is None:
            self._trainer = make_trainer(self.sim_cfg, self.data,
                                         mesh=self.mesh,
                                         donate=self.donate,
                                         model=self.model)
        return self._trainer

    def _put1(self, arr):
        """Place one (N,) per-client array (sharded under the mesh)."""
        return place_per_client(arr, self.mesh)

    def _fresh_caches(self, template):
        """Empty (N, ...) C3 cache state for a new run.

        With ``donate_buffers``, the previous run's final caches (stashed
        on ``_last_caches``) are recycled: a donated jitted reset memsets
        zeros/-1 into the existing fleet buffers in place, so back-to-back
        runs skip re-faulting the O(N·D) cache pytree — at N=4096 the
        fresh allocation costs ~7x the in-place reset.  Sharding carries
        through (``zeros_like`` keeps the donated leaves' placement)."""
        N = self.fl_cfg.num_clients
        spent, self._last_caches = self._last_caches, None
        if self.offload is not None:
            # offload: params live in the host store — reset it (and any
            # write-back still in flight) and keep only (N,) metadata on
            # device; the reset-recycling below applies unchanged to the
            # metadata-only pytree
            self._cache_stream.reset()
            template = {}
        if self.donate and spent is not None:
            if self._cache_reset is None:
                self._cache_reset = jax.jit(core.reset_caches,
                                            donate_argnums=0)
            return self._cache_reset(spent)
        caches = core.init_caches(template, N)
        if self.mesh is not None:
            caches = SP.place_fleet(caches, self.mesh, N)
        return caches

    def _server_step(self, uses_cache: bool):
        # keyed on mesh shape + donation + cohort so ``run(policy)``
        # reuse stays valid if the engine's placement knobs ever diverge
        # per run (the cohort key is what memoizes the compact (X, D)
        # step separately from the full-scan one)
        mesh_key = None if self.mesh is None else \
            tuple(self.mesh.devices.shape)
        key = (bool(uses_cache), mesh_key, self.donate, self.cohort,
               self.offload)
        if key not in self._server_steps:
            self._server_steps[key] = core.make_server_round_step(
                self._template, local_steps=self.sim_cfg.local_steps,
                agg_impl=self.fl_cfg.agg_impl,
                agg_rule=self.fl_cfg.agg_rule,
                agg_rule_params=self.fl_cfg.agg_rule_params,
                adversary_scale=self._adv_scale,
                staleness_discount=self.fl_cfg.staleness_discount,
                uses_cache=bool(uses_cache),
                block_c=self.fl_cfg.agg_block_c,
                block_d=self.fl_cfg.agg_block_d, mesh=self.mesh,
                donate=self.donate, cohort_size=self.cohort,
                cache_offload=self.offload)
        return self._server_steps[key]

    # -- telemetry plumbing (repro.obs) -------------------------------------

    @property
    def transfer_stats(self) -> "core.TransferStats":
        """This engine's cache-stream transfer counters (all zero when
        no offload stream is configured).  Strictly per-engine, so
        concurrent engines never clobber each other's counts; the
        static per-round ceiling these must respect lives in
        ``repro.analysis.audit.transfer_ceiling``."""
        return self._transfer_stats

    # -- debug_checks sanitizers (repro.analysis.runtime) --------------------

    def _debug_round_check(self, global_params, losses, idx, rnd):
        """``FLConfig.debug_checks`` round guard: checkify the post-step
        global model / losses for non-finite values and the cohort index
        for OOB.  Reads one error scalar back per round — the sanitizer's
        documented host sync, never active on production runs."""
        from repro.analysis import runtime as RT
        if self._round_guard is None:
            self._round_guard = RT.make_round_guard(
                self.fl_cfg.num_clients, with_idx=idx is not None)
        err, _ = self._round_guard(global_params, losses) if idx is None \
            else self._round_guard(global_params, losses, idx)
        RT.throw_round_error(err, rnd)

    def _debug_recompile_check(self):
        """``FLConfig.debug_checks`` run-end assertion: none of the
        engine's memoized jitted dispatches re-traced across runs."""
        from repro.analysis import runtime as RT
        if self._recomp_detector is None:
            self._recomp_detector = RT.RecompilationDetector(self)
        self._recomp_detector.check()

    def _resolve_telemetry(self, arg):
        """``run(telemetry=...)`` -> ``Telemetry | None``.

        ``None`` defers to ``FLConfig.telemetry`` (a bare session at
        that level, metrics land on ``History.metrics``); ``False``
        forces telemetry off for this run; a level string builds a bare
        session; a ``repro.obs.Telemetry`` is used as-is (sinks, trace
        paths and profiler window included).  Level ``"spans"`` traces
        the seams alone: ``_metrics_fn`` builds no metrics dispatch."""
        if arg is False:
            return None
        if arg is None:
            lvl = self.fl_cfg.telemetry
            return None if lvl is None else obs.Telemetry(level=lvl)
        if isinstance(arg, str):
            return obs.Telemetry(level=arg)
        return arg

    def _metrics_fn(self, level: str, uses_cache: bool,
                    rows_bound: Optional[int] = None):
        """Memoized fused metrics dispatch for the active round path:
        ``(jitted fn, needed ctx keys)`` — ``(None, ())`` when nothing
        applies.  The availability set advertises exactly what the
        path produces, so registered metrics with unmet needs are never
        traced.  ``rows_bound`` is the policy's static selection bound
        on the full-scan path (rows there are the fleet-sized (N, ...)
        stack): O(rows · D) metrics use it to gather the received rows
        into a compact block before reducing."""
        if level == "spans":
            return None, ()
        key = (level, self.cohort, self.offload, self._agg_stateful,
               bool(uses_cache), rows_bound)
        if key not in self._metrics_fns:
            avail = {"selected", "distribute", "resume", "online",
                     "received", "fail", "losses", "times", "progress",
                     "stamp", "rnd", "rows", "rows_mask", "global"}
            if self.cohort is not None:
                avail.add("cohort_size")
                if self.model.expert_load is not None:
                    avail.add("expert_load")
            if self._agg_stateful:
                avail.add("rule_state")
            if self.offload == "discard" and uses_cache:
                avail.add("stamp_pre_expire")
            static = {"num_clients": self.fl_cfg.num_clients,
                      "cohort_size": self.cohort,
                      "local_steps": self.sim_cfg.local_steps,
                      "staleness_edges": obs.metrics.STALENESS_EDGES,
                      "rows_bound": rows_bound}
            self._metrics_fns[key] = obs.make_metrics_fn(
                level, avail, static, mesh=self.mesh)
        return self._metrics_fns[key]

    def _metrics_dispatch(self, metrics_fn, m_keys, tracer, rnd,
                          global_params, caches, rule_state,
                          stamp_pre_expire, **cand):
        """Issue the fused metrics dispatch.  Must be called *before*
        the round's server step: with ``donate_buffers`` the step
        consumes (invalidates) the pre-step global model and cache
        metadata the reductions read."""
        if metrics_fn is None:
            return None
        cand.update(progress=caches.progress, stamp=caches.round_stamp,
                    rnd=rnd)
        cand["global"] = global_params
        if rule_state is not None:
            cand["rule_state"] = rule_state
        if stamp_pre_expire is not None:
            cand["stamp_pre_expire"] = stamp_pre_expire
        with tracer.span("metrics", round=rnd):
            if "expert_load" in m_keys:
                cand["expert_load"] = self._expert_load_jit()(
                    global_params, self._frozen, self._x_dev, cand["idx"])
            return metrics_fn({k: cand[k] for k in m_keys})

    def _expert_load_jit(self):
        """Memoized jit of the model's held-expert load on the cohort
        block's first local batch (telemetry only)."""
        if self._load_fn is None:
            b = min(self.sim_cfg.batch_size, self.data.x.shape[1])
            load = self.model.expert_load

            @jax.jit
            def load_fn(params, frozen, x_all, idx):
                x = jnp.take(x_all, idx, axis=0, mode="fill",
                             fill_value=0)[:, :b]
                return load(params, frozen, x)

            self._load_fn = load_fn
            self._x_dev = jnp.asarray(self.data.x)
        return self._load_fn

    # -- robust-aggregation state / adversary plumbing ----------------------

    def _init_rule_state(self):
        """Fresh per-run (N,) rule state (stateful rules only), placed
        on device (sharded under the mesh) — the only fleet-state the
        robust axis adds, threaded through the step like the caches."""
        if not self._agg_stateful:
            return None
        return self._put1(self._agg_rule.init_state(
            self.fl_cfg.num_clients))

    def _step_extra(self, rule_state):
        """Trailing args of the fused server step: the device-resident
        malicious mask (adversary configured), then the rule state."""
        extra = ()
        if self._adv_scale is not None:
            extra += (self._malicious,)
        if self._agg_stateful:
            extra += (rule_state,)
        return extra

    def server_step_memory(self, uses_cache: bool = True) -> dict:
        """Allocation profile of the compiled fused server step (bytes).

        Lowers the step on representative round inputs and reads XLA's
        memory analysis.  With ``donate_buffers`` the previous global
        model + caches alias into the outputs (``alias_bytes`` > 0), so
        the steady-state peak — arguments + outputs + temps − aliased —
        drops by exactly the persistent fleet state the step no longer
        double-buffers.

        The profile describes the *active* step: with
        ``FLConfig.cohort_size`` set, the stacked trainer outputs and the
        packed aggregation buffer are (X, ...) cohort blocks, not (N, ...)
        — ``packed_rows``/``packed_buffer_bytes`` report which buffer
        actually lives on device.

        Beyond the XLA analysis, the profile reports the engine's
        persistent fleet-state residency: ``rule_state_bytes`` (the
        stateful robust-aggregation (N,) vector, 0 for stateless rules)
        and the C3 cache split ``cache_device_bytes`` /
        ``cache_host_bytes`` — resident mode keeps the whole (N, D)
        pytree on device and 0 bytes on host; under ``cache_offload``
        the device holds only (N,) metadata plus the (X, D) cohort
        block (O(X·D), fleet-size-independent) and the host side is the
        store's current live rows.
        """
        lowered, caches, rule_state = self._lower_server_step(uses_cache)
        ma = lowered.compile().memory_analysis()
        out = {"argument_bytes": int(ma.argument_size_in_bytes),
               "output_bytes": int(ma.output_size_in_bytes),
               "temp_bytes": int(ma.temp_size_in_bytes),
               "alias_bytes": int(ma.alias_size_in_bytes)}
        out["peak_live_bytes"] = (out["argument_bytes"]
                                  + out["output_bytes"]
                                  + out["temp_bytes"]
                                  - out["alias_bytes"])
        rows = self.fl_cfg.num_clients if self.cohort is None \
            else int(self.cohort)
        meta_only = self.offload is not None
        layout = core.pack_layout(self._template)
        out["packed_rows"] = rows
        out["packed_buffer_bytes"] = layout.buffer_bytes(rows)

        def tree_bytes(tree):
            return sum(int(np.prod(np.shape(l), dtype=np.int64))
                       * np.dtype(jnp.asarray(l).dtype).itemsize
                       for l in jax.tree.leaves(tree))

        out["rule_state_bytes"] = 0 if rule_state is None \
            else tree_bytes(rule_state)
        meta_bytes = tree_bytes((caches.progress, caches.round_stamp))
        if meta_only:
            # device residency: (N,) metadata + the per-round (X, D)
            # cohort slot block — O(X·D), independent of fleet size
            out["cache_device_bytes"] = meta_bytes \
                + rows * self.cache_store.row_bytes
            out["cache_host_bytes"] = self.cache_store.nbytes
        else:
            out["cache_device_bytes"] = meta_bytes \
                + tree_bytes(caches.params)
            out["cache_host_bytes"] = 0
        return out

    def compiled_server_step(self, uses_cache: bool = True):
        """The fused server step compiled for representative round
        inputs: ``as_text()`` shows which aggregation path it lowered to
        (a ``tpu_custom_call`` per Pallas kernel under
        ``agg_impl="pallas"`` on a TPU)."""
        return self._lower_server_step(uses_cache)[0].compile()

    def _lower_server_step(self, uses_cache: bool):
        """Lower the active server step on representative round inputs;
        returns ``(lowered, caches, rule_state)``.  ``lower()`` only
        traces — nothing executes, nothing is donated."""
        N = self.fl_cfg.num_clients
        rows = N if self.cohort is None else int(self.cohort)
        step = self._server_step(uses_cache)
        meta_only = self.offload is not None
        caches = core.init_caches({} if meta_only else self._template, N)
        stacked = jax.tree.map(
            lambda a: jnp.zeros((rows,) + a.shape, a.dtype),
            self._template)
        if self.mesh is not None:
            caches = SP.place_fleet(caches, self.mesh, N)
            stacked = SP.place_fleet(stacked, self.mesh, rows)
        mask = self._put1(np.zeros(rows, bool))
        steps_i = self._put1(np.zeros(rows, np.int32))
        ones = self._put1(np.ones(N, np.float32))
        rule_state = self._init_rule_state()
        extra = self._step_extra(rule_state)
        if self.cohort is None:
            lowered = step.lower(self._template, caches, stacked, stacked,
                                 steps_i, mask, mask, mask, mask,
                                 self._n_samples, ones, 0, *extra)
        elif meta_only:
            idx = self._put1(np.arange(rows, dtype=np.int32))
            mask_n = self._put1(np.zeros(N, bool))
            lowered = step.lower(self._template, caches, stacked,
                                 steps_i, idx, mask_n, mask, mask, mask_n,
                                 self._n_samples, ones, 0, *extra)
        else:
            idx = self._put1(np.arange(rows, dtype=np.int32))
            mask_n = self._put1(np.zeros(N, bool))
            lowered = step.lower(self._template, caches, stacked, stacked,
                                 steps_i, idx, mask_n, mask, mask, mask_n,
                                 self._n_samples, ones, 0, *extra)
        return lowered, caches, rule_state

    def run(self, policy: Union[str, Policy], rounds: Optional[int] = None,
            time_budget: Optional[float] = None, eval_every: int = 1,
            progress: Optional[Callable] = None,
            diagnostics: bool = True, telemetry=None) -> History:
        """Run FL rounds.  ``time_budget`` (simulated seconds) caps the run
        by wall clock instead of round count — the paper's comparison
        regime: faster policies (shorter rounds) fit more rounds in the
        same budget.  ``rounds`` (default ``sim_cfg.rounds``) remains the
        hard round cap.  ``diagnostics=False`` skips the O(N)-eval
        end-of-run per-class/per-client accuracy sweep (benchmarks).

        ``FLConfig.dynamics`` picks the availability process: the default
        ``bernoulli_host`` runs the seed simulator's host-RNG loop
        (bit-identical golden trajectories); every other registered
        process (``repro.fleet``) runs the device-resident loop — draws,
        workload, failures, timing AND the round cut are produced on
        device, sharded over the client mesh, with no per-round
        host→device hand-off.  On that loop ``FLConfig.pipeline_depth``
        > 1 keeps up to depth-1 rounds of bookkeeping in flight (History
        is read back at eval boundaries and run end), overlapping round
        k+1's dispatches with round k's device execution; trajectories
        are bit-identical at every depth.

        ``telemetry`` (see ``_resolve_telemetry``): ``None`` defers to
        ``FLConfig.telemetry``, a level string or ``repro.obs.Telemetry``
        enables device metrics + host span tracing for this run
        (``"spans"``: tracing alone), and ``False`` forces it off.
        Metric values ride the round ledger's existing readback, so the
        trajectory is bit-identical (and the per-round host-sync count
        unchanged) with telemetry on or off."""
        sim_cfg, fl_cfg = self.sim_cfg, self.fl_cfg
        fleet = self._fleet if self._fleet is not None else Fleet(sim_cfg)
        if isinstance(policy, str):
            policy = make_policy(policy, sim_cfg, fl_cfg, fleet,
                                 mesh=self.mesh)
        if self.cohort is not None:
            bound = policy.selection_bound()
            if bound > self.cohort:
                raise ValueError(
                    f"policy {policy.name!r} can select up to {bound} "
                    f"clients per round but FLConfig.cohort_size="
                    f"{self.cohort} — the compact round path would "
                    f"truncate its cohort.  Raise cohort_size to at "
                    f"least {bound} (or set it to None for the full "
                    f"scan).")
        state = policy.init_state()
        n_rounds = sim_cfg.rounds if rounds is None else rounds

        rng = jax.random.key(sim_cfg.seed)
        global_params = self._template
        if self.donate:
            # the first round's server step donates its global-model input;
            # the template must survive for subsequent run() calls
            global_params = jax.tree.map(jnp.copy, global_params)
        caches = self._fresh_caches(global_params)

        hist = History()
        tel = self._resolve_telemetry(telemetry)
        tracer = tel.tracer if tel is not None else obs.NULL_TRACER
        self._tracer = tracer       # seams outside the loops (placement)
        if self._cache_stream is not None:
            self._cache_stream.tracer = tracer
        if tel is not None:
            tel.open_run({"policy": policy.name,
                          "num_clients": fl_cfg.num_clients,
                          "rounds": n_rounds,
                          "dynamics": fl_cfg.dynamics,
                          "cohort_size": fl_cfg.cohort_size,
                          "cache_offload": fl_cfg.cache_offload,
                          "pipeline_depth": fl_cfg.pipeline_depth})
            hist.metrics = {}
        rounds_loop = self._host_rounds \
            if get_dynamics(fl_cfg.dynamics).host_side \
            else self._device_rounds
        with tracer.span("rounds"):
            state, global_params, caches = rounds_loop(
                policy, state, fleet, hist, global_params, caches, rng,
                n_rounds, time_budget, eval_every, progress, tel)
        if self.debug_checks:
            self._debug_recompile_check()

        # a time_budget break can land between eval boundaries, leaving
        # the final booked round with a stale carried-forward (or NaN)
        # accuracy — force a measurement on the final global model so
        # time/comm_to_accuracy and "final acc" reports see fresh data
        if time_budget is not None and hist.eval_mask \
                and not hist.eval_mask[-1]:
            hist.acc[-1] = float(self._acc_fn(global_params, self._frozen,
                                              self._test_x, self._test_y))
            hist.eval_mask[-1] = True

        # final diagnostics (paper Fig. 1(b)(c))
        if diagnostics and self.model.per_class_accuracy is not None:
            with tracer.span("diagnostics"):
                hist.per_class_acc = np.asarray(
                    self.model.per_class_accuracy(
                        global_params, self._frozen, self._test_x,
                        self._test_y, self.data.num_classes))
                pc = []
                for i in range(min(fl_cfg.num_clients,
                                   self.data.x.shape[0])):
                    pc.append(float(self._acc_fn(
                        global_params, self._frozen,
                        jnp.asarray(self.data.x[i]),
                        jnp.asarray(self.data.y[i]))))
                hist.per_client_acc = np.asarray(pc)
        for k, v in policy.history_extras(state).items():
            setattr(hist, k, v)
        if self._agg_stateful:
            # final per-client trust scores (stateful robust rules): the
            # read-back happens once, at run end — rounds stay sync-free
            setattr(hist, "trust",
                    np.asarray(jax.device_get(self._last_rule_state)))
        if tel is not None:
            final_acc = hist.acc[-1] if hist.acc else None
            tel.close_run({
                "policy": policy.name, "rounds": len(hist.acc),
                "final_acc": None if final_acc is None
                or final_acc != final_acc else final_acc,
                "comm_mb": hist.comm_mb[-1] if hist.comm_mb else 0.0,
                "wall_clock": hist.wall_clock[-1] if hist.wall_clock
                else 0.0,
                "transfer_stats": self._transfer_stats.snapshot()})
            self._tracer = obs.NULL_TRACER
            if self._cache_stream is not None:
                self._cache_stream.tracer = obs.NULL_TRACER
        hist.final_params = global_params
        # final device-resident fleet state (stays sharded under the mesh;
        # the seam for multi-round pipelining / warm restarts)
        self._last_caches = caches
        return hist

    # -- shared host-side round closing / bookkeeping -----------------------

    def _close_round(self, times, plan, policy):
        """Round termination (Algorithm 2 lines 13–16) on the per-device
        finish times — the host numpy path, kept for the legacy host-RNG
        loop (and as the property-test reference of the jitted cut)."""
        return core.host_round_cut(times, float(np.asarray(plan.quorum)),
                                   self.sim_cfg.round_deadline,
                                   policy.waits_for_stragglers)

    def _round_cut(self, waits_for_stragglers: bool):
        """Memoized jitted device round cut (one variant per the policy's
        straggler trait), everything device-resident.  With a cohort the
        cut runs over the (X,) gathered finish times and additionally
        scatters the (N,) receive mask (every finite time belongs to a
        cohort member, so the order statistics — and the cut — are
        exact).  Built with ``with_counts=True``: the cut also returns
        the round's (received, download, selected) ledger counts as
        device scalars, fused into the same dispatch — the loop hands
        them straight to the ledger, so per-round host bookkeeping is
        O(1) scalar handles instead of an extra (N,)-reducing jit."""
        key = (bool(waits_for_stragglers), self.cohort)
        if key not in self._cut_fns:
            if self.cohort is None:
                self._cut_fns[key] = core.make_round_cut(
                    self.fl_cfg.num_clients, self.sim_cfg.round_deadline,
                    key[0], mesh=self.mesh, with_counts=True)
            else:
                self._cut_fns[key] = core.make_round_cut(
                    self.cohort, self.sim_cfg.round_deadline, key[0],
                    mesh=self.mesh,
                    scatter_num_clients=self.fl_cfg.num_clients,
                    with_counts=True)
        return self._cut_fns[key]

    def _validate_plan(self, plan):
        """Per-round plan admission, shared by both loops.  Plans built
        through ``RoundPlan.create``/``RoundPlan.device`` already ran
        their checks — only fleet-size agreement (and, for host-side
        overrides, the scan-length cap) is left to confirm."""
        fl_cfg, sim_cfg = self.fl_cfg, self.sim_cfg
        if getattr(plan, "_validated", False):
            if plan.selected.shape[0] != fl_cfg.num_clients:
                raise ValueError(
                    f"RoundPlan sized {plan.selected.shape[0]} for a "
                    f"{fl_cfg.num_clients}-client fleet")
            so = plan.steps_override
            if so is not None and not isinstance(so, jax.Array) \
                    and np.asarray(so).size \
                    and int(np.asarray(so).max()) > sim_cfg.local_steps:
                raise ValueError(
                    f"RoundPlan.steps_override requests up to "
                    f"{int(np.asarray(so).max())} local steps but the "
                    f"trainer scans only {sim_cfg.local_steps}")
        else:
            plan.validate(fl_cfg.num_clients,
                          local_steps=sim_cfg.local_steps)

    def _book_round(self, hist, rnd, n_rounds, eval_every, global_params,
                    downloads, received, selected, duration, cum_comm,
                    cum_time, acc, progress):
        """Comm/time accumulation, eval cadence and the History appends
        for one round; returns the updated ``(cum_comm, cum_time, acc)``.
        ``downloads``/``received``/``selected`` are host (N,) bools —
        ``downloads`` is the distribute mask already gated by the round's
        online mask (§4.4 only transmits to reachable devices)."""
        cum_comm += (downloads.sum() + received.sum()) \
            * self.sim_cfg.model_mb
        cum_time += duration
        evaluated = rnd % eval_every == 0 or rnd == n_rounds - 1
        if evaluated:
            acc = float(self._acc_fn(global_params, self._frozen,
                                     self._test_x, self._test_y))
        hist.acc.append(acc)
        hist.eval_mask.append(evaluated)
        hist.comm_mb.append(cum_comm)
        hist.wall_clock.append(cum_time)
        hist.received.append(int(received.sum()))
        hist.selected.append(int(selected.sum()))
        if progress and (rnd % 10 == 0 or rnd == n_rounds - 1):
            progress(rnd, acc, cum_comm, cum_time)
        return cum_comm, cum_time, acc

    # -- legacy host-RNG round loop (bernoulli_host) ------------------------

    def _host_rounds(self, policy, state, fleet, hist, global_params,
                     caches, rng, n_rounds, time_budget, eval_every,
                     progress, tel=None):
        """The seed simulator's numpy round loop — draw-for-draw identical
        to the pre-dynamics engine, so the golden trajectories of every
        registered policy stay bit-identical."""
        sim_cfg, fl_cfg = self.sim_cfg, self.fl_cfg
        n_samples = self._n_samples
        tracer = tel.tracer if tel is not None else obs.NULL_TRACER
        metrics_fn, m_keys = (None, ()) if tel is None else \
            self._metrics_fn(tel.level, policy.uses_cache,
                             rows_bound=policy.selection_bound())

        # adaptive cache frequency (C3): steps between cache writes
        cache_every_np = np.clip(np.round(
            core.adaptive_cache_interval(2.0, fleet.battery,
                                         fleet.stability)), 1, 4
        ).astype(np.int32) if policy.uses_cache else \
            np.full(fl_cfg.num_clients, BIG, np.int32)
        cache_every = self._put1(cache_every_np)

        cum_comm = 0.0
        cum_time = 0.0
        acc = float("nan")
        full_steps = np.full(fl_cfg.num_clients, sim_cfg.local_steps,
                             np.int32)
        ones_w = self._put1(np.ones((fl_cfg.num_clients,), np.float32))
        server_step = self._server_step(policy.uses_cache)
        rule_state = self._init_rule_state()

        for rnd in tracer.steps("round", n_rounds):
            if time_budget is not None and cum_time >= time_budget:
                break
            rng, k_sel = jax.random.split(rng)
            online = fleet.online_mask()
            with tracer.span("plan", round=rnd):
                state, plan = policy.plan(
                    state, RoundObservation(rnd, online, caches), k_sel)
            self._validate_plan(plan)
            selected = np.asarray(plan.selected)
            distribute = np.asarray(plan.distribute)
            resume = np.asarray(plan.resume)

            # per-device workload (override clamped to the scan length)
            prior_steps = np.round(
                np.asarray(caches.progress) * sim_cfg.local_steps
            ).astype(np.int32)
            base_steps = full_steps if plan.steps_override is None \
                else np.minimum(np.asarray(plan.steps_override),
                                sim_cfg.local_steps)
            steps_needed = np.where(resume,
                                    np.maximum(base_steps - prior_steps, 1),
                                    base_steps).astype(np.int32)
            steps_needed = np.where(selected, steps_needed, 0)

            # failures (exposure-scaled) + interruption points
            fail = fleet.failure_draw(
                steps_needed / max(sim_cfg.local_steps, 1))
            fail &= selected
            stop = np.where(fail, fleet.failure_step(steps_needed), BIG)

            # local training; the start state (fresh global vs cached
            # local) is selected on device inside the jitted trainer
            with tracer.span("trainer", round=rnd):
                final, cache_p, cached_steps, losses = self.trainer(
                    global_params, caches, self._put1(resume),
                    self._put1(steps_needed), self._put1(stop),
                    cache_every, self._frozen)

            # timing + round termination
            success = selected & ~fail & (steps_needed > 0)
            completed = np.minimum(steps_needed, stop)
            times = fleet.round_times(steps_needed, distribute, completed,
                                      success)
            t_cut, duration = self._close_round(times, plan, policy)
            received = success & (times <= t_cut)

            # fused server step (§4.3 hot path): aggregation weights with
            # the staleness discount for stale BASE models, packed
            # whole-model weighted aggregation, C3 cache write/clear —
            # one jitted call, params never leave the device.
            extra_w = ones_w if plan.agg_weights is None else \
                self._put1(np.asarray(plan.agg_weights, np.float32))
            # fused metrics dispatch (telemetry on): reductions over the
            # pre-step state — the legacy loop is host-synchronous, so
            # values are read back within the round below
            mx = self._metrics_dispatch(
                metrics_fn, m_keys, tracer, rnd, global_params, caches,
                rule_state, None, selected=selected, distribute=distribute,
                resume=resume, online=online, received=received,
                fail=fail, losses=losses, times=times, rows=final,
                rows_mask=received)
            with tracer.span("server_step", round=rnd):
                out = server_step(
                    global_params, caches, final, cache_p, cached_steps,
                    self._put1(selected), self._put1(fail),
                    self._put1(received), self._put1(resume),
                    n_samples, extra_w, rnd,
                    *self._step_extra(rule_state))
            if self._agg_stateful:
                global_params, caches, rule_state = out
            else:
                global_params, caches = out

            if self.debug_checks:
                self._debug_round_check(global_params, losses, None, rnd)
            with tracer.span("observe", round=rnd):
                state = policy.observe(
                    state, plan,
                    RoundReport(received=received, fail=fail,
                                losses=np.asarray(losses),
                                durations=times, duration=duration,
                                rnd=rnd))

            cum_comm, cum_time, acc = self._book_round(
                hist, rnd, n_rounds, eval_every, global_params,
                distribute & online, received, selected, duration,
                cum_comm, cum_time, acc, progress)
            if tel is not None:
                vals = {} if mx is None else \
                    {k: _metric_py(v) for k, v in
                     jax.device_get(mx).items()}
                if vals:
                    for k, v in vals.items():
                        hist.metrics.setdefault(k, []).append(v)
                tel.record_round({
                    "round": rnd, "evaluated": bool(hist.eval_mask[-1]),
                    "acc": None if acc != acc else acc,
                    "duration": float(duration), "comm_mb": cum_comm,
                    "wall_clock": cum_time,
                    "received": int(received.sum()),
                    "downloads": int((distribute & online).sum()),
                    "selected": int(selected.sum()), **vals})

        self._last_rule_state = rule_state
        return state, global_params, caches

    # -- device-resident dynamics round loop (repro.fleet) ------------------

    def _dynamics_fns(self, fleet):
        """Memoized device-dynamics artifacts for the configured process:
        (process, jitted init, jitted step, fused dynamics trainer).  The
        jitted step applies the fleet sharding constraint so draws stay
        sharded over the client mesh no matter what the process body
        produced.  (The round cut is memoized separately per straggler
        trait — see ``_round_cut``.)"""
        key = (self.fl_cfg.dynamics, self.fl_cfg.dynamics_params,
               self.cohort, self.offload)
        if key not in self._dyn_cache:
            N = self.fl_cfg.num_clients
            mesh = self.mesh
            feats = fleet.features(mesh)
            process = make_dynamics(self.fl_cfg.dynamics, self.sim_cfg,
                                    features=feats, mesh=mesh,
                                    params=self.fl_cfg.dynamics_params)

            def step(fstate, k):
                s, d = process.step(fstate, k)
                return (SP.fleet_constraint(s, mesh, N),
                        SP.fleet_constraint(d, mesh, N))

            def dynamics_init(k):
                return SP.fleet_constraint(process.init_state(k), mesh, N)

            init_fn = jax.jit(dynamics_init)
            trainer = make_trainer(
                self.sim_cfg, self.data, mesh=mesh,
                dynamics_features=feats, cohort_size=self.cohort,
                external_cache_params=self.offload is not None,
                model=self.model)
            self._dyn_cache[key] = (process, init_fn, jax.jit(step),
                                    trainer)
        return self._dyn_cache[key]

    def _dyn_consts(self, fleet, uses_cache):
        """Per-run (N,) constants, placed once and reused across runs —
        steady-state dynamics rounds upload nothing."""
        key = ("cache_every", bool(uses_cache))
        if key not in self._round_consts:
            N = self.fl_cfg.num_clients
            ce = np.clip(np.round(core.adaptive_cache_interval(
                2.0, fleet.battery, fleet.stability)), 1, 4
            ).astype(np.int32) if uses_cache else np.full(N, BIG, np.int32)
            self._round_consts[key] = self._put1(ce)
        if "ones" not in self._round_consts:
            N = self.fl_cfg.num_clients
            self._round_consts["ones"] = self._put1(
                np.ones(N, np.float32))
            self._round_consts["full_steps"] = self._put1(
                np.full(N, self.sim_cfg.local_steps, np.int32))
        return (self._round_consts[key], self._round_consts["ones"],
                self._round_consts["full_steps"])

    def _from_plan(self, arr, dtype=None):
        """One (N,) plan field onto the fleet.  Device-native plans
        (flude) pass through untouched; host-side policy arrays cost one
        upload — the *draws* are device-resident either way."""
        if isinstance(arr, jax.Array):
            return arr
        with self._tracer.span("place_per_client"):
            return self._put1(np.asarray(arr) if dtype is None
                              else np.asarray(arr, dtype))

    # -- cache-offload round plumbing ----------------------------------------

    def _offload_idx_fn(self):
        """Memoized jit deriving the round's cohort index + overflow flag
        from the selection mask.  On the resident path this lives inside
        the trainer jit; the offload path needs the index *before* the
        trainer runs (the host-store fetch consumes it), so it gets its
        own small dispatch — same ``cohort_index`` computation, so the
        values (and everything downstream) are identical."""
        if self._idx_fn is None:
            X, mesh = int(self.cohort), self.mesh

            @jax.jit
            def idx_fn(selected):
                idx = SP.cohort_constraint(cohort_index(selected, X),
                                           mesh, X)
                overflow, = SP.replicated_constraint(
                    (cohort_overflow(selected, X),), mesh)
                return idx, overflow

            self._idx_fn = idx_fn
        return self._idx_fn

    def _expire_fn_jit(self):
        """Memoized jit of the device-side discard expiry (metadata-only
        ``core.expire_caches`` with the configured bound)."""
        if self._expire_fn is None:
            mesh, N = self.mesh, self.fl_cfg.num_clients
            bound = int(self.fl_cfg.cache_staleness_bound)

            @jax.jit
            def expire_fn(caches, rnd):
                return SP.fleet_constraint(
                    core.expire_caches(caches, rnd, bound), mesh, N)

            self._expire_fn = expire_fn
        return self._expire_fn

    def _zero_cohort_block(self):
        """Memoized all-zero (X, ...) cache block for policies that never
        cache (``uses_cache=False``): the resident path would gather the
        never-written zero pytree, so a constant zeros block placed once
        keeps the offload trainer's inputs — and its rounds — identical,
        with no per-round transfer at all."""
        if self._zeros_x is None:
            X = int(self.cohort)
            block = jax.tree.map(
                lambda a: jnp.zeros((X,) + a.shape, a.dtype),
                self._template)
            if self.mesh is not None:
                block = jax.device_put(block, jax.tree.map(
                    lambda l: SP.cohort_sharding(self.mesh, l.ndim),
                    block))
            self._zeros_x = block
        return self._zeros_x

    def _device_rounds(self, policy, state, fleet, hist, global_params,
                       caches, rng, n_rounds, time_budget, eval_every,
                       progress, tel=None):
        """Dynamics round loop: the round's availability/failure draw,
        workload, local training, timing model AND the quorum cut run on
        device (sharded over the client mesh) — process step, fused
        trainer, round cut, fused server step, four dispatches with no
        host value in between.  Bookkeeping is deferred through a
        ``_RoundLedger``: History rows are read back only when the
        pipeline depth forces it, at eval boundaries (the accuracy
        scalar), or at run end — with ``pipeline_depth`` > 1 the host
        dispatches round k+1 while round k still executes.  jnp-native
        policies (flude) keep even planning on device; host-side policies
        sync at their own ``np.asarray`` boundaries as before."""
        sim_cfg = self.sim_cfg
        n_samples = self._n_samples
        process, init_fn, step_fn, trainer = self._dynamics_fns(fleet)
        cache_every, ones_w, full_steps = self._dyn_consts(
            fleet, policy.uses_cache)
        server_step = self._server_step(policy.uses_cache)
        rule_state = self._init_rule_state()
        cut_fn = self._round_cut(policy.waits_for_stragglers)
        cohort_info = None if self.cohort is None \
            else (policy.name, self.cohort)
        tracer = tel.tracer if tel is not None else obs.NULL_TRACER
        # cohort rows are already the compact (X, ...) block; the full
        # scan advertises the policy's selection bound so O(rows · D)
        # metrics gather received rows instead of reading all N
        metrics_fn, m_keys = (None, ()) if tel is None else \
            self._metrics_fn(tel.level, policy.uses_cache,
                             rows_bound=None if self.cohort is not None
                             else policy.selection_bound())
        ledger = _RoundLedger(hist, sim_cfg.model_mb,
                              sim_cfg.round_deadline, progress, n_rounds,
                              cohort_info=cohort_info, telemetry=tel,
                              tracer=tracer)

        # independent dynamics key stream, reproducible per run
        dyn_base = jax.random.fold_in(jax.random.key(sim_cfg.seed),
                                      0x0F1EE7)
        fstate = init_fn(jax.random.fold_in(dyn_base, 1 << 20))

        draw = None
        for rnd in tracer.steps("round", n_rounds):
            if time_budget is not None:
                # the budget check needs cum_time: resolve everything
                # in flight (budget runs are effectively depth 1)
                ledger.resolve()
                if ledger.cum_time >= time_budget:
                    break
            if tel is not None:
                tel.maybe_profile(rnd)
            rng, k_sel = jax.random.split(rng)
            with tracer.span("dynamics_step", round=rnd):
                fstate, draw = step_fn(fstate,
                                       jax.random.fold_in(dyn_base, rnd))
            stamp_pre_expire = None
            if self.offload == "discard" and policy.uses_cache:
                # device half of the discard bound: expire stale cache
                # metadata *before* planning reads it, so the planner
                # never resumes a row the host store prunes (the store
                # prunes with the same bound at write-back drain).  The
                # pre-expiry stamps stay live for the metrics dispatch
                # (cache_expired counts; the expire jit donates nothing)
                if metrics_fn is not None:
                    stamp_pre_expire = caches.round_stamp
                with tracer.span("cache_expire", round=rnd):
                    caches = self._expire_fn_jit()(caches, rnd)
            with tracer.span("plan", round=rnd):
                state, plan = policy.plan(
                    state, RoundObservation(rnd, draw.online, caches,
                                            draw=draw), k_sel)
            self._validate_plan(plan)
            sel_d = self._from_plan(plan.selected)
            dist_d = self._from_plan(plan.distribute)
            res_d = self._from_plan(plan.resume)
            base_steps = full_steps if plan.steps_override is None else \
                self._from_plan(plan.steps_override, np.int32)

            extra_w = ones_w if plan.agg_weights is None else \
                self._from_plan(plan.agg_weights, np.float32)
            if self.cohort is None:
                # fused round body: workload + failure/interruption +
                # masked local training + per-device timing, one dispatch
                with tracer.span("trainer", round=rnd):
                    (final, cache_p, cached_steps, losses, steps_needed,
                     fail, success, times) = trainer(
                        global_params, caches, draw, sel_d, dist_d,
                        res_d, base_steps, cache_every, self._frozen)

                # round termination on device: the cut is a device scalar
                # and the receive mask stays sharded; deadline-capped
                # rounds come back as a flag so the ledger bills the
                # exact f64 deadline.  The ledger counts ride the same
                # dispatch (``with_counts``).
                with tracer.span("round_cut", round=rnd):
                    (t_cut, received, capped, recv_n, down_n,
                     sel_n) = cut_fn(times, plan.quorum, success,
                                     draw.online, dist_d, sel_d)
                overflow = None
                mx = self._metrics_dispatch(
                    metrics_fn, m_keys, tracer, rnd, global_params,
                    caches, rule_state, stamp_pre_expire,
                    selected=sel_d, distribute=dist_d, resume=res_d,
                    online=draw.online, received=received, fail=fail,
                    losses=losses, times=times, rows=final,
                    rows_mask=received)
                with tracer.span("server_step", round=rnd):
                    out = server_step(
                        global_params, caches, final, cache_p,
                        cached_steps, sel_d, fail, received, res_d,
                        n_samples, extra_w, rnd,
                        *self._step_extra(rule_state))
                if self._agg_stateful:
                    global_params, caches, rule_state = out
                else:
                    global_params, caches = out
                report = RoundReport(received=received, fail=fail,
                                     losses=losses, durations=times,
                                     duration=t_cut, rnd=rnd)
            elif self.offload is None:
                # compact cohort: the trainer gathers the selected rows
                # into (X, ...) blocks on device and hands back scattered
                # (N,) report views; cut + aggregation run over X rows
                with tracer.span("trainer", round=rnd):
                    (final, cache_p, cached_steps, _losses_x, _steps_x,
                     fail, success, times, idx, overflow, losses_n,
                     fail_n, times_n) = trainer(
                        global_params, caches, draw, sel_d, dist_d,
                        res_d, base_steps, cache_every, self._frozen)
                with tracer.span("round_cut", round=rnd):
                    (t_cut, _received_x, received, capped, recv_n,
                     down_n, sel_n) = cut_fn(times, plan.quorum, success,
                                             idx, draw.online, dist_d,
                                             sel_d)
                # observability seam (tests / debugging): the last
                # round's device cohort index, still sharded
                self._last_cohort_idx = idx
                mx = self._metrics_dispatch(
                    metrics_fn, m_keys, tracer, rnd, global_params,
                    caches, rule_state, stamp_pre_expire,
                    selected=sel_d, distribute=dist_d, resume=res_d,
                    online=draw.online, received=received, fail=fail_n,
                    losses=losses_n, times=times_n, rows=final,
                    rows_mask=_received_x, idx=idx)
                with tracer.span("server_step", round=rnd):
                    out = server_step(
                        global_params, caches, final, cache_p,
                        cached_steps, idx, sel_d, fail, _received_x,
                        res_d, n_samples, extra_w, rnd,
                        *self._step_extra(rule_state))
                if self._agg_stateful:
                    global_params, caches, rule_state = out
                else:
                    global_params, caches = out
                report = RoundReport(received=received, fail=fail_n,
                                     losses=losses_n, durations=times_n,
                                     duration=t_cut, rnd=rnd)
            else:
                # host-offloaded cohort caches: derive the cohort index
                # in its own small jit so the host can start streaming
                # the cohort's cache rows (async d2h of idx, drain of
                # last round's write-back, async device_put of the (X,
                # ...) block) while this round's other dispatches are
                # being issued; the trainer/cut/server step are the same
                # cohort ops over the same rows, so trajectories stay
                # bit-identical to the resident path
                with tracer.span("cohort_index", round=rnd):
                    idx, overflow = self._offload_idx_fn()(sel_d)
                if policy.uses_cache:
                    with tracer.span("cache_fetch", round=rnd):
                        cache_x = self._cache_stream.fetch(idx, rnd)
                else:
                    cache_x = self._zero_cohort_block()
                with tracer.span("trainer", round=rnd):
                    (final, cache_p, cached_steps, _losses_x, _steps_x,
                     fail, success, times, losses_n, fail_n,
                     times_n) = trainer(
                        global_params, caches, cache_x, idx, draw, sel_d,
                        dist_d, res_d, base_steps, cache_every,
                        self._frozen)
                with tracer.span("round_cut", round=rnd):
                    (t_cut, _received_x, received, capped, recv_n,
                     down_n, sel_n) = cut_fn(times, plan.quorum, success,
                                             idx, draw.online, dist_d,
                                             sel_d)
                self._last_cohort_idx = idx
                mx = self._metrics_dispatch(
                    metrics_fn, m_keys, tracer, rnd, global_params,
                    caches, rule_state, stamp_pre_expire,
                    selected=sel_d, distribute=dist_d, resume=res_d,
                    online=draw.online, received=received, fail=fail_n,
                    losses=losses_n, times=times_n, rows=final,
                    rows_mask=_received_x, idx=idx)
                with tracer.span("server_step", round=rnd):
                    out = server_step(
                        global_params, caches, final, cached_steps, idx,
                        sel_d, fail, _received_x, res_d, n_samples,
                        extra_w, rnd, *self._step_extra(rule_state))
                if self._agg_stateful:
                    (global_params, caches, write_x, stamp_x,
                     rule_state) = out
                else:
                    global_params, caches, write_x, stamp_x = out
                if policy.uses_cache:
                    # park the round's write-back: async copies start
                    # now, nothing blocks until next round's fetch
                    with tracer.span("cache_stage", round=rnd):
                        self._cache_stream.stage(idx, write_x,
                                                 _received_x, cache_p,
                                                 stamp_x)
                # the round's (X, D) blocks are dead once dispatched (the
                # stream holds the staged one until its drain): drop them,
                # so that they are not live under the next round's fetch
                # and trainer
                del cache_x, final, cache_p
                report = RoundReport(received=received, fail=fail_n,
                                     losses=losses_n, durations=times_n,
                                     duration=t_cut, rnd=rnd)

            if self.debug_checks:
                self._debug_round_check(
                    global_params, report.losses,
                    None if self.cohort is None else idx, rnd)
            with tracer.span("observe", round=rnd):
                state = policy.observe(state, plan, report)

            evaluated = rnd % eval_every == 0 or rnd == n_rounds - 1
            acc_dev = None
            if evaluated:
                with tracer.span("eval", round=rnd):
                    acc_dev = self._acc_fn(global_params, self._frozen,
                                           self._test_x, self._test_y)
            ledger.push(rnd, evaluated, t_cut, capped, recv_n,
                        down_n, sel_n, acc_dev, overflow=overflow,
                        metrics=mx)
            if progress and rnd % 10 == 0:
                ledger.resolve()        # live ticks resolve on schedule
            else:
                ledger.resolve(keep=self.pipeline_depth - 1)

        ledger.resolve()
        if self._cache_stream is not None:
            # apply the last round's parked write-back so the host store
            # reflects the final cache state (its copies have been in
            # flight since that round's server step was dispatched)
            with tracer.span("cache_flush"):
                self._cache_stream.flush(n_rounds)
        # pipelining seam: the process state (and last draw) stay
        # device-resident between runs, like the caches
        self._last_fleet_state = fstate
        self._last_draw = draw
        self._last_rule_state = rule_state
        return state, global_params, caches
