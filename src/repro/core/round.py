"""C5 — the FLUDE round process (paper §4.4, Algorithm 2), server side.

``plan_round`` runs lines 3–12: budget-adaptive participant count X,
Algorithm-1 selection, staleness-aware distribution, predicted comm cost.
``update_after_round`` runs the post-aggregation bookkeeping: Beta-posterior
updates (Eq. 1), participation counters (Eq. 3 numerator), U/V membership,
ε decay.  Both are pure jnp over fixed-shape fleet arrays.

``make_server_round_step`` builds the fused per-round server step: weight
computation (incl. staleness discount), packed single-kernel aggregation,
and cache write/clear in ONE jitted call — the per-round hot path (§4.3)
stays on device with no per-leaf dispatch or host round-trips.

Round *termination* (lines 13–16: first |S|·R̄ uploads or deadline T)
lives here too: ``host_round_cut`` is the numpy reference (the legacy
host-RNG loop still runs it), ``make_round_cut`` is the jitted
device-resident equivalent the engine's dynamics loop dispatches — the
cut, billed duration and receive mask never leave the device, which is
what lets the loop pipeline rounds (``FLConfig.pipeline_depth``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FLConfig
from repro.core import aggregation as AGG
from repro.core import caching as C
from repro.core import distribution as D
from repro.core import selection as SEL
from repro.core.dependability import BetaBelief, dependability, init_belief


class FludeState(NamedTuple):
    """Full server-side fleet state (a jit-able pytree)."""
    belief: BetaBelief
    part_count: jax.Array       # (N,) int32 — q_i
    explored: jax.Array         # (N,) bool — C
    in_v: jax.Array             # (N,) bool — failed last participation
    distributor: D.DistributorState
    epsilon: jax.Array          # scalar
    total_selected: jax.Array   # scalar — Σ_k |S_k|
    round: jax.Array            # scalar int32


class FludePlan(NamedTuple):
    selected: jax.Array         # (N,) bool — S
    distribute: jax.Array       # (N,) bool — S_distr (fresh global model)
    resume: jax.Array           # (N,) bool — train from local cache
    predicted_cost: jax.Array   # scalar — B_pred (model transmissions)
    quorum: jax.Array           # scalar — |S| · R̄ receive cutoff
    avg_dependability: jax.Array
    priority: jax.Array         # (N,) — P(i), for logging
    distributor: D.DistributorState


def init_state(cfg: FLConfig) -> FludeState:
    return FludeState(
        belief=init_belief(cfg.num_clients, cfg.beta_alpha0, cfg.beta_beta0),
        part_count=jnp.zeros((cfg.num_clients,), jnp.int32),
        explored=jnp.zeros((cfg.num_clients,), bool),
        in_v=jnp.zeros((cfg.num_clients,), bool),
        distributor=D.init_distributor(cfg.w_init),
        epsilon=jnp.float32(cfg.epsilon_init),
        total_selected=jnp.float32(0.0),
        round=jnp.int32(0),
    )


def _plan_once(state: FludeState, caches: C.ClientCaches,
               online: jax.Array, X, cfg: FLConfig, rng,
               explore_hints=None) -> FludePlan:
    sel = SEL.select_participants(
        state.belief, state.part_count, state.explored, online,
        state.total_selected, X, state.epsilon, cfg.sigma, rng,
        explore_hints=explore_hints, mode=cfg.selection_mode)
    stale = C.staleness(caches, state.round)
    plan = D.plan_distribution(
        state.distributor, sel.selected, state.in_v, C.has_cache(caches),
        stale, lam=cfg.lam, mu=cfg.mu, w_min=cfg.w_min, w_max=cfg.w_max,
        mode=cfg.distribution_mode)
    r_sel = jnp.where(sel.selected, dependability(state.belief), 0.0)
    n_sel = jnp.maximum(sel.selected.sum(), 1)
    r_bar = r_sel.sum() / n_sel
    cost = D.predicted_comm_cost(plan.distribute, sel.selected, r_bar)
    # floor: with quorum = ceil(|S|·R̄), ~half the rounds have fewer
    # successes than the quorum and idle-wait the full deadline T —
    # exactly the waste Algorithm 2 is designed to avoid
    quorum = jnp.maximum(jnp.floor(sel.selected.sum() * r_bar), 1.0)
    return FludePlan(sel.selected, plan.distribute, plan.resume, cost,
                     quorum, r_bar, sel.priority, plan.state)


def plan_round(state: FludeState, caches: C.ClientCaches,
               online: jax.Array, cfg: FLConfig, rng,
               max_budget_iters: int = 8,
               explore_hints=None) -> FludePlan:
    """Algorithm 2 lines 3–11: shrink X until B_pred ≤ B_max.

    ``explore_hints``: optional (N,) device-status scores (battery ×
    stability) biasing exploration order — §4.1's optional heuristic."""
    X = jnp.minimum(jnp.int32(cfg.clients_per_round), online.sum())
    plan = _plan_once(state, caches, online, X, cfg, rng, explore_hints)
    if cfg.comm_budget == float("inf"):
        return plan
    b_max = jnp.float32(cfg.comm_budget)
    for _ in range(max_budget_iters):
        X = jnp.where(plan.predicted_cost > b_max,
                      jnp.maximum(
                          (X * b_max / jnp.maximum(plan.predicted_cost, 1e-9)
                           ).astype(jnp.int32), 1),
                      X)
        plan = _plan_once(state, caches, online, X, cfg, rng,
                          explore_hints)
    return plan


def make_server_round_step(template_params, *, local_steps: int,
                           agg_impl: str = "xla",
                           agg_rule: str = "mean",
                           agg_rule_params: tuple = (),
                           adversary_scale: Optional[float] = None,
                           staleness_discount: float = 1.0,
                           uses_cache: bool = True,
                           block_c: int = 8, block_d: int = 2048,
                           mesh=None, donate: bool = False):
    """Build the fused per-round server step (one jit, zero host syncs).

    The returned callable runs everything the server does between "uploads
    arrived" and "next round plans": aggregation weights (sample-count ×
    staleness discount for resumed bases, §4.3), the packed whole-model
    weighted aggregation, and C3 cache bookkeeping (write failed devices'
    progress, clear received slots).

    template_params: the *unstacked* global model pytree — fixes the packed
    (C, D) layout once.  ``uses_cache=False`` policies get an identity
    cache path (compiled out).

    The step runs over the round's rows, which its inputs name: ``idx``
    is the (X,) cohort index (sentinel-padded) or None, the identity —
    all N rows in order, with no gather or scatter.  Given an index, the
    (N,) plan masks, cache metadata and weights are gathered into (X,)
    blocks, aggregation packs and reduces an (X, D) buffer, and the
    cache writes scatter back into the (N,) fleet state (predicated
    ``.at[].set(mode="drop")`` writes: sentinel and masked-off rows touch
    nothing).  Weighted aggregation over the gathered rows is the same
    sequential fp32 reduction over the same nonzero-weight terms, so a
    single-device cohort round is bit-identical to the full scan; under
    a client mesh cohort members regroup across shards and the psum
    reassociates (integer trajectory exact, accuracies to float
    tolerance).

    Where the cache rows live is read from ``caches`` too: with resident
    params the step writes ``cache_rows`` (the trainer's cache block) into
    the pytree; with an empty params pytree (host-offloaded caches, the
    rows in ``repro.core.cache_store``) only the metadata changes, and the
    engine stages ``cache_rows`` to the host store with the returned
    ``write``/``base_round``.

    ``mesh``: optional fleet mesh with a ``clients`` axis — the packed
    (C, D) buffer aggregates as per-shard partial sums + psum and the
    cache bookkeeping stays sharded.  ``donate=True`` donates the previous
    global model and the caches: every output (new global, new caches)
    then aliases a donated input and the step allocates nothing persistent
    — the packed (C, D) buffer lives only as jit-internal workspace.  The
    stacked trainer outputs are deliberately NOT donated: the one stacked
    output slot (new cache params) is already served by the donated
    caches, so donating them could never alias and would only raise
    jax's unusable-donation warning.  Donated host handles (the caller's
    previous global/caches references) are invalidated by the call.

    ``agg_rule`` / ``agg_rule_params``: the robust-aggregation axis
    (``repro.core.agg_rules``), orthogonal to ``agg_impl``.  The default
    ``"mean"`` keeps the historical direct ``fed_aggregate_packed`` call
    — the traced jaxpr (and therefore the trajectory) is bit-identical
    to the pre-registry step.  Non-mean rules pack the stacked trainer
    outputs once and run the rule's reduction on the (C, D) buffer; a
    *stateful* rule ("trust") takes one (N,) state input and returns it
    updated — the engine threads it like the caches, so rounds still
    sync nothing.

    ``adversary_scale``: when set, the returned step additionally takes
    an (N,) malicious mask (appended after ``rnd``, before any rule
    state) and transforms the marked clients' uploads inside the jit:
    ``u' = g + adversary_scale * (u - g)`` — the model-poisoning channel
    of ``repro.fleet.adversary``.  Benign runs compile the attack out.
    """
    from repro.sharding import partitioning as SP

    layout = AGG.pack_layout(template_params)
    donate_argnums = (0, 1) if donate else ()
    rule = None
    if agg_rule not in (None, "mean"):
        from repro.core.agg_rules import make_agg_rule
        rule = make_agg_rule(agg_rule, agg_rule_params)
    stateful = rule is not None and rule.stateful
    has_adv = adversary_scale is not None

    def poison(final_params, global_params, mal_rows):
        """Model-poisoning transform on the malicious rows (stacked
        leaves), against the round's base model."""
        s = float(adversary_scale)

        def pz(f, g):
            m = mal_rows.reshape((-1,) + (1,) * (f.ndim - 1))
            g32 = g.astype(jnp.float32)[None]
            return jnp.where(m, (g32 + s * (f.astype(jnp.float32) - g32))
                             .astype(f.dtype), f)

        return jax.tree.map(pz, final_params, global_params)

    def aggregate(global_params, final_params, w, rule_state):
        """Dispatch the configured rule.  Returns ``(new_global,
        new_rule_state)`` — state rows pass through untouched for
        stateless rules."""
        if rule is None:
            new_global = AGG.fed_aggregate_packed(
                global_params, final_params, w, layout, impl=agg_impl,
                block_c=block_c, block_d=block_d, mesh=mesh)
            return new_global, rule_state
        buf = AGG.pack_stacked(final_params, layout)     # (C, D) fp32
        gvec = AGG.pack(global_params, layout)           # (D,) fp32
        kw = dict(impl=agg_impl, block_c=block_c, block_d=block_d,
                  mesh=mesh)
        if stateful:
            vec, rule_state = rule.reduce_stateful(buf, gvec, w,
                                                   rule_state, **kw)
        else:
            vec = rule.reduce(buf, gvec, w, **kw)
        any_received = w.sum() > 0
        new_global = jax.tree.map(
            lambda avg, g: jnp.where(any_received, avg, g),
            AGG.unpack(vec, layout), global_params)
        return new_global, rule_state

    def split_extra(extra):
        """(malicious, rule_state) from the trailing jit args."""
        expect = int(has_adv) + int(stateful)
        if len(extra) != expect:
            raise TypeError(
                f"server round step expects {expect} trailing arg(s) "
                f"(adversary mask: {has_adv}, rule state: {stateful}), "
                f"got {len(extra)}")
        malicious = extra[0] if has_adv else None
        rule_state = extra[-1] if stateful else None
        return malicious, rule_state

    @functools.partial(jax.jit, donate_argnums=donate_argnums)
    def server_round_step(global_params, caches: C.ClientCaches,
                          final_params, cache_rows, cached_steps, idx,
                          selected, fail, received, resume, n_samples,
                          extra_weights, rnd, *extra):
        """-> (new_global, new_caches, write, base_round, new_rule_state).

        final_params / cache_rows / cached_steps and the ``fail``/
        ``received`` masks are the round's row blocks (trainer / round-cut
        outputs): (X, ...) under a cohort index ``idx``, (N, ...) when
        ``idx`` is None.  ``selected``/``resume`` are the (N,) plan masks
        and are gathered here; caches / n_samples / extra_weights stay
        (N,)-sized.  ``extra_weights``: (N,) policy weight multiplier
        (ones if unused).  ``rnd``: scalar int32, the current round.
        ``extra`` appends the (N,) malicious mask (adversary configured)
        and the (N,) rule state (stateful rule).

        ``write``/``base_round`` are the rows' cache-write mask and round
        stamps (what the engine stages to the host store under offload);
        ``new_rule_state`` is None for stateless rules.
        """
        malicious, rule_state = split_extra(extra)
        rnd = jnp.asarray(rnd, jnp.int32)
        rows = received.shape[0]

        def take(a, fill):
            return C.take_rows(a, idx, fill)

        def pin(tree, num):
            if idx is None:
                return tree
            return SP.fleet_constraint(tree, mesh, num)

        selected = take(selected, False)
        resume = take(resume, False)
        stamp = take(caches.round_stamp, -1)
        # staleness of the BASE model each update was trained from
        base_stale = jnp.where(resume & (stamp >= 0),
                               jnp.maximum(rnd - stamp, 0),
                               0).astype(jnp.float32)
        w = AGG.aggregation_weights(
            received, n_samples=take(n_samples, 0.0),
            staleness=base_stale,
            staleness_discount=staleness_discount) \
            * take(extra_weights, 0.0)
        w = pin(w, rows)
        if has_adv:
            final_params = poison(final_params, global_params,
                                  pin(take(malicious, False), rows))
        state_x = None
        if stateful:
            state_x = pin(take(rule_state, 0.0), rows)
        new_global, state_x = aggregate(global_params, final_params, w,
                                        state_x)
        if stateful:
            rule_state = state_x if idx is None else \
                rule_state.at[idx].set(state_x, mode="drop")
            rule_state = pin(rule_state, rule_state.shape[0])
        if uses_cache:
            prior_steps = jnp.round(
                take(caches.progress, 0.0) * local_steps).astype(jnp.int32)
            total_cached = jnp.where(resume, prior_steps, 0) + cached_steps
            write = selected & fail & (total_cached > 0)
            base_round = jnp.where(resume & (stamp >= 0), stamp, rnd)
            # offloaded caches carry no params: the same predicated
            # writes then touch only progress / round_stamp
            new_rows = cache_rows if jax.tree.leaves(caches.params) \
                else caches.params
            caches = C.scatter_write_cache(
                caches, idx, write, new_rows,
                (total_cached / max(local_steps, 1)).astype(jnp.float32),
                base_round)
            caches = C.scatter_clear_cache(caches, idx, received)
            caches = pin(caches, caches.progress.shape[0])
        else:
            write = jnp.zeros((rows,), bool)
            base_round = jnp.full((rows,), -1, jnp.int32)
        write, base_round = pin((write, base_round), rows)
        return new_global, caches, write, base_round, rule_state

    return server_round_step


def host_round_cut(times, quorum, round_deadline: float,
                   waits_for_stragglers: bool):
    """Round termination (Algorithm 2 lines 13–16), numpy reference.

    ``times``: (N,) per-device finish times, inf where the device never
    uploads.  The round closes at the ``ceil(quorum)``-th upload (capped
    by the deadline T); async/semi-async designs
    (``waits_for_stragglers=False``) close at the last arrival when the
    quorum is not met; otherwise the server idle-waits the full deadline.
    Returns ``(t_cut, duration)`` — ``duration`` is the billed round wall
    clock (always finite when the deadline is).
    """
    times = np.asarray(times)
    q = int(np.ceil(float(quorum)))
    finite = np.sort(times[np.isfinite(times)])
    if finite.size >= q and q > 0:
        t_cut = min(float(finite[q - 1]), round_deadline)
    elif not waits_for_stragglers and finite.size > 0:
        t_cut = min(float(finite[-1]), round_deadline)
    else:
        t_cut = round_deadline
    duration = t_cut if np.isfinite(t_cut) else round_deadline
    return t_cut, duration


def make_round_cut(num_clients: int, round_deadline: float,
                   waits_for_stragglers: bool, mesh=None):
    """Build the jitted device-resident round cut (lines 13–16).

    Semantically identical to :func:`host_round_cut` — and bit-identical
    on float32 times (property-tested in tests/test_round_close*.py):
    uncapped cuts are exact float32 arrival times, and deadline-capped
    rounds are flagged instead of billed in float32.  The returned
    callable maps ``(times, quorum, success)`` to ``(t_cut, received,
    capped)``:

    * ``t_cut`` — float32 device scalar; the billed host-side duration is
      ``round_deadline if capped else float(t_cut)`` (the host reference
      bills the *float64* deadline, which float32 cannot always
      represent — e.g. ``round_deadline=100.3`` — so the cap is returned
      as a flag and the ledger substitutes the exact config value);
    * ``received`` — the receive mask over the rows of ``times``, pinned
      to the client-mesh sharding when ``mesh`` is given.  Deadline-capped
      rounds compare against the float32-*nearest* cast of the deadline —
      exactly what the pre-pipelining loop's jitted ``times <= cut`` did
      with the host's float64 cut, so depth-1 receive masks stay
      bit-identical;
    * ``capped`` — bool device scalar: the round idle-waited (or closed
      at) the deadline rather than an arrival.  The flag itself is exact
      (``t > deadline`` decided via the largest float32 ≤ deadline).

    A positive quorum counts at least one upload, however small: XLA
    flushes float32 subnormals to zero, so positivity is read from the
    quorum's bits, which are never flushed (the host's ``ceil`` gives 1
    there too).

    Everything stays on device, so the engine can dispatch the server
    step — and further rounds — without draining the device queue.
    ``waits_for_stragglers`` is a static policy trait: the async variant
    compiles the extra close-at-last-arrival branch in, the sync variant
    compiles it out.

    The engine's form passes two more inputs: the (X,) cohort index
    ``idx`` the rows of ``times``/``success`` were gathered by (None: the
    rows are the fleet's ``num_clients`` in order), then the (N,) masks
    ``(online, distribute, selected)``.  It returns ``(t_cut, received,
    received_full, capped, received_count, download_count,
    selected_count)``: ``received_full`` is the (N,) receive mask
    (scattered back onto the fleet through ``idx``, sentinel rows
    dropped; ``received`` itself when ``idx`` is None), and the three
    device scalars are the round's History ledger counts
    (``download_count`` is ``(distribute & online).sum()``), fused into
    the cut so everything the deferred History needs leaves it as O(1)
    replicated scalars.  The cut over cohort rows is exact: every finite
    finish time belongs to a selected client, selected ⊆ cohort, so the
    order statistics over the X rows equal those over the full N —
    bit-identical even under a mesh.
    """
    from repro.sharding import partitioning as SP

    deadline = float(round_deadline)
    # nearest float32 (what the old received_fn's weak f64->f32 cast did)
    d_cmp = np.float32(deadline)
    # largest float32 <= deadline: for float32 t, (t > d_flag) == (t > d)
    d_flag = d_cmp
    if float(d_flag) > deadline:
        d_flag = np.nextafter(d_flag, np.float32(-np.inf))

    def cut_core(times, quorum, success):
        rows = times.shape[0]
        q = jnp.asarray(quorum, jnp.float32)
        positive = jax.lax.bitcast_convert_type(q, jnp.int32) > 0
        q = jnp.where(positive,
                      jnp.maximum(jnp.ceil(q).astype(jnp.int32), 1), 0)
        order = jnp.sort(times)                   # inf sorts to the end
        finite_count = jnp.isfinite(times).sum()
        t_quorum = order[jnp.clip(q - 1, 0, rows - 1)]
        has_quorum = (finite_count >= q) & (q > 0)
        t_raw = jnp.where(has_quorum, t_quorum, jnp.inf)
        if not waits_for_stragglers:
            # async/semi-async designs close at the last arrival
            t_last = order[jnp.clip(finite_count - 1, 0, rows - 1)]
            t_raw = jnp.where(~has_quorum & (finite_count > 0), t_last,
                              t_raw)
        capped = t_raw > d_flag
        t_cut = jnp.where(capped, d_cmp, t_raw)
        received = success & (times <= t_cut)
        return t_cut, received, capped

    @jax.jit
    def round_cut(times, quorum, success, idx=None, *masks):
        t_cut, received, capped = cut_core(times, quorum, success)
        received = SP.fleet_constraint(received, mesh, times.shape[0])
        t_cut, capped = SP.replicated_constraint((t_cut, capped), mesh)
        if not masks:
            return t_cut, received, capped
        received_full = received
        if idx is not None:
            received_full = SP.cohort_scatter_constraint(
                jnp.zeros((num_clients,), bool).at[idx].set(
                    received, mode="drop"), mesh, num_clients)
        online, distribute, selected = masks
        # sentinel rows are never received, so the rows' sum is the
        # fleet's
        counts = SP.replicated_constraint(
            (received.sum(), (distribute & online).sum(), selected.sum()),
            mesh)
        return (t_cut, received, received_full, capped) + counts

    return round_cut


def receive_quorum(plan: FludePlan) -> jax.Array:
    """Line 15 cutoff: the round ends after ⌈|S|·R̄⌉ received uploads."""
    return plan.quorum


def update_after_round(state: FludeState, plan: FludePlan,
                       received: jax.Array, cfg: FLConfig) -> FludeState:
    """Post-round bookkeeping.  received: (N,) bool — uploaded in time."""
    sel = plan.selected
    success = sel & received
    failure = sel & ~received
    belief = BetaBelief(state.belief.alpha + success.astype(jnp.float32),
                        state.belief.beta + failure.astype(jnp.float32))
    explored = state.explored | sel
    in_v = jnp.where(sel, failure, state.in_v)
    return FludeState(
        belief=belief,
        part_count=state.part_count + sel.astype(jnp.int32),
        explored=explored,
        in_v=in_v,
        distributor=plan.distributor,
        epsilon=SEL.decay_epsilon(state.epsilon, cfg.epsilon_decay,
                                  cfg.epsilon_min),
        total_selected=state.total_selected
        + sel.sum().astype(jnp.float32),
        round=state.round + 1,
    )
