"""Plain reference of a FleetEngine run, written from the semantics alone.

It imports nothing of the program under test and takes nothing the
program made: the fleet population, the availability process, the
policy, the round cut, the cache bookkeeping and the training are all
rebuilt here from the configuration and the seed, in straightforward
``jax.numpy`` (numpy where the semantics are host numpy).  The data
and the model (its leaves, initial values and loss) are the
configuration's model file's, ``models/<kind>.py``, which the spec
carries as ``model_code``.

Two parts:

* the fleet simulation, over every round of a run: availability draws,
  selection (FLUDE Algorithms 1-2 with Eq. 1-4, or MIFA's select-all),
  workload, failures, per-device finish times, the quorum cut and the
  cache metadata.  None of it depends on model values, so it is exact
  and compared exactly: it runs in float32 with the same operations as
  the semantics state them;
* the model, over the first ``numeric_rounds`` rounds: local SGD on the
  selected clients (resumed from their cached state where the plan
  says so), the staleness-discounted aggregation weights, the poisoned
  uploads of an adversary, and the weighted mean or the smoothed
  Weiszfeld geometric median.  Only the trainable leaves are trained,
  packed, aggregated and cached; the frozen ones are made once from the
  seed and held fixed.  Matrix products run at ``HIGHEST`` precision in
  the reference dtype; ``dtype=bfloat16`` gives the control, and
  ``fault="half_batch"`` leaves the second half of each round's
  received clients out of the aggregate.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

BIG = 1 << 20
NEG = -1e30
TINY = 1e-30
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# Packing: trainable leaves <-> the (D,) rows uploads and caches hold
# ---------------------------------------------------------------------------

def pack(params: dict, names) -> jax.Array:
    """(C, ...) leaves -> (C, D) rows, leaves in flattening order."""
    c = params[names[0]].shape[0]
    return jnp.concatenate([params[n].reshape(c, -1).astype(jnp.float32)
                            for n in names], axis=1)


def unpack(vec, shapes: dict) -> dict:
    """(..., D) -> leaves of shape (...,) + leaf shape."""
    out, off = {}, 0
    lead = tuple(vec.shape[:-1])
    for name, shape in shapes.items():
        n = int(np.prod(shape))
        out[name] = vec[..., off:off + n].reshape(lead + tuple(shape))
        off += n
    return out


def _to_ref(a, dtype):
    """``a`` in the reference dtype where it is floating; integer arrays
    (token ids, index tables) keep their dtype."""
    a = jnp.asarray(a)
    return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a


# ---------------------------------------------------------------------------
# Fleet population and availability processes
# ---------------------------------------------------------------------------

def fleet_profile(sim: dict) -> Dict[str, np.ndarray]:
    """The static population: the seed's numpy stream, drawn in order."""
    rng = np.random.RandomState(int(sim["seed"]))
    n = int(sim["num_clients"])
    means = np.asarray(sim["undep_means"])
    group = rng.randint(0, len(means), n)
    undep = np.clip(rng.randn(n) * sim["undep_std"] + means[group],
                    0.02, 0.98)
    online_rate = rng.uniform(sim["online_low"], sim["online_high"], n)
    tiers = np.asarray(sim["steps_per_sec"])
    tier = rng.randint(0, len(tiers), n)
    steps_per_sec = tiers[tier] * rng.uniform(0.8, 1.2, n)
    lo, hi = sim["bandwidth_mbps"]
    bandwidth = rng.uniform(lo, hi, n)
    battery = rng.uniform(0.2, 1.0, n)
    stability = rng.uniform(0.3, 1.0, n)
    return dict(undep=undep, online_rate=online_rate,
                steps_per_sec=steps_per_sec, bandwidth=bandwidth,
                battery=battery, stability=stability)


class Draw(NamedTuple):
    online: Any
    fail_p: Any
    fail_u: Any
    stop_u: Any
    bandwidth: Any


def _weibull(key, shape, scale, k):
    u = jax.random.uniform(key, shape, minval=1e-7, maxval=1.0)
    return scale * jnp.power(-jnp.log1p(-u), 1.0 / k)


def make_process(traffic: dict, n: int):
    """(consts(feats) -> dict, init(feats, consts, key) -> slot,
    step(feats, consts, t, slot, key) -> (slot, Draw)).

    ``consts`` runs op by op outside any jit, as the semantics state the
    per-device constants; ``init`` and ``step`` take the population as
    arguments, so their compiled programs do not depend on the seed."""
    kind = traffic["dynamics"]
    p = dict(traffic.get("dynamics_params", {}))

    def base_draw(feats, key, online, fail_p):
        k_fail, k_stop = jax.random.split(key)
        return Draw(online, fail_p, jax.random.uniform(k_fail, (n,)),
                    jax.random.uniform(k_stop, (n,)), feats["bandwidth"])

    if kind == "bernoulli":
        def consts(feats):
            return {}

        def init(feats, c, key):
            return ()

        def step(feats, c, t, slot, key):
            k_on, k_draw = jax.random.split(key)
            u = jax.random.uniform(k_on, (n,))
            online = u < feats["online_rate"]
            return slot, base_draw(feats, k_draw, online, feats["undep"])

        return consts, init, step

    if kind != "sessions":
        raise ValueError(f"the reference has no availability process "
                         f"{kind!r}")
    mean_on = float(p.get("mean_on", 4.0))
    shape_on = float(p.get("shape_on", 1.0))
    shape_gap = float(p.get("shape_gap", 1.0))
    amp = float(p.get("amp", 0.0))
    period = float(p.get("period", 24.0))
    phase = float(p.get("phase", 0.0))
    undep_mix = float(p.get("undep_mix", 0.0))
    scale_on = mean_on / math.gamma(1.0 + 1.0 / shape_on)

    def consts(feats):
        r = feats["online_rate"]
        mean_gap = mean_on * (1.0 - r) / r
        return {"scale_gap": mean_gap / math.gamma(1.0 + 1.0 / shape_gap)}

    def hazard(age):
        return 1.0 - jnp.exp(jnp.power(age / scale_on, shape_on)
                             - jnp.power((age + 1.0) / scale_on, shape_on))

    def init(feats, c, key):
        k_on, k_dur = jax.random.split(key)
        on0 = jax.random.uniform(k_on, (n,)) < feats["online_rate"]
        dur_on = _weibull(k_dur, (n,), scale_on, shape_on)
        dur_gap = _weibull(jax.random.fold_in(k_dur, 1), (n,),
                           c["scale_gap"], shape_gap)
        return {"on": on0, "remaining": jnp.where(on0, dur_on, dur_gap),
                "age": jnp.zeros((n,), jnp.float32)}

    def step(feats, c, t, slot, key):
        k_on, k_gap, k_draw = jax.random.split(key, 3)
        remaining = slot["remaining"] - 1.0
        expired = remaining <= 0.0
        on = jnp.where(expired, ~slot["on"], slot["on"])
        new_on = _weibull(k_on, (n,), scale_on, shape_on)
        diurnal = 1.0 + amp * jnp.cos(2.0 * jnp.pi * (t - phase) / period)
        new_gap = _weibull(k_gap, (n,), c["scale_gap"] * diurnal, shape_gap)
        remaining = jnp.where(expired, jnp.where(on, new_on, new_gap),
                              remaining)
        age = jnp.where(expired, 0.0, slot["age"] + 1.0)
        fail_p = 1.0 - (1.0 - hazard(age)) \
            * (1.0 - undep_mix * feats["undep"])
        return ({"on": on, "remaining": remaining, "age": age},
                base_draw(feats, k_draw, on, fail_p.astype(jnp.float32)))

    return consts, init, step


def malicious_mask(n: int, seed: int, frac: float) -> np.ndarray:
    rng = np.random.RandomState((int(seed) + 0xAD5) % (2 ** 31))
    k = int(round(frac * n))
    mask = np.zeros(n, bool)
    mask[rng.permutation(n)[:k]] = True
    return mask


# ---------------------------------------------------------------------------
# Policies
# ---------------------------------------------------------------------------

def _rank_mask(scores, k):
    order = jnp.argsort(-scores)
    ranks = jnp.zeros_like(order).at[order].set(jnp.arange(scores.shape[0]))
    return (ranks < k) & (scores > NEG / 2)


def flude_init(fl: dict, n: int) -> dict:
    return dict(alpha=jnp.full((n,), fl["beta_alpha0"], jnp.float32),
                beta=jnp.full((n,), fl["beta_beta0"], jnp.float32),
                part=jnp.zeros((n,), jnp.int32),
                explored=jnp.zeros((n,), bool),
                in_v=jnp.zeros((n,), bool),
                w=jnp.float32(fl["w_init"]), h_old=jnp.float32(0.0),
                n_old=jnp.float32(1.0),
                eps=jnp.float32(fl["epsilon_init"]),
                total=jnp.float32(0.0), rnd=jnp.int32(0))


def flude_plan(st, stamp, online, rng, hints, fl: dict):
    """Algorithm 1 (selection) and 2 (distribution, quorum), unlimited
    budget.  Returns (selected, distribute, resume, quorum, dist_state)."""
    n = online.shape[0]
    R = st["alpha"] / (st["alpha"] + st["beta"])
    Q = st["total"] / max(n, 1)
    q = st["part"].astype(jnp.float32)
    ratio = jnp.where(q > 0, Q / jnp.maximum(q, 1e-9), 1.0)
    exceeds = (q > Q).astype(jnp.float32)
    P = R * jnp.power(jnp.maximum(ratio, 1e-9), exceeds * fl["sigma"])
    X = jnp.minimum(jnp.int32(fl["clients_per_round"]), online.sum())
    X = jnp.minimum(X, online.sum())
    n_explore_want = jnp.round(st["eps"] * X).astype(jnp.int32)
    pool_explore = (~st["explored"]) & online
    pool_exploit = st["explored"] & online
    n_explore = jnp.minimum(n_explore_want, pool_explore.sum())
    n_exploit = jnp.minimum(X - n_explore, pool_exploit.sum())
    n_explore = jnp.minimum(X - n_exploit, pool_explore.sum())
    exploited = _rank_mask(jnp.where(pool_exploit, P, NEG), n_exploit)
    noise = hints + 0.01 * jax.random.uniform(rng, (n,))
    explored_new = _rank_mask(jnp.where(pool_explore, noise, NEG),
                              n_explore)
    sel = exploited | explored_new
    # Eq. 4: staleness-aware distribution
    has_cache = stamp >= 0
    stale = jnp.where(~has_cache, jnp.int32(1 << 20),
                      st["rnd"] - stamp).astype(jnp.float32)
    cacheable = sel & st["in_v"] & has_cache
    nv = jnp.maximum(cacheable.sum(), 1)
    h_new = jnp.where(cacheable, stale, 0.0).sum() / nv
    w_old, h_old, n_old = st["w"], st["h_old"], st["n_old"]
    h_ref = jnp.where(h_old > 0, h_old, jnp.maximum(h_new, 1e-3))
    delta_h = jnp.where(h_old > 0, h_new - h_old, 0.0)
    w_prime = w_old * (1.0 - fl["lam"] * delta_h / h_ref)
    n_new = (cacheable & (stale > w_prime)).sum().astype(jnp.float32)
    w_new = w_prime * (1.0 + fl["mu"] * (n_new - n_old)
                       / jnp.maximum(n_old, 1.0))
    w_new = jnp.clip(w_new, fl["w_min"], fl["w_max"])
    resume = cacheable & ~(stale > w_new)
    distribute = sel & ~resume
    r_bar = jnp.where(sel, R, 0.0).sum() / jnp.maximum(sel.sum(), 1)
    quorum = jnp.maximum(jnp.floor(sel.sum() * r_bar), 1.0)
    quorum = jnp.minimum(quorum, sel.sum().astype(jnp.float32))
    return sel, distribute, resume, quorum, (w_new, h_new, n_new)


def flude_update(st, sel, received, dist_state, fl: dict) -> dict:
    """Eq. 1 beliefs, Eq. 3 counts, U/V membership, epsilon decay."""
    success = sel & received
    failure = sel & ~received
    w, h, nn = dist_state
    return dict(alpha=st["alpha"] + success.astype(jnp.float32),
                beta=st["beta"] + failure.astype(jnp.float32),
                part=st["part"] + sel.astype(jnp.int32),
                explored=st["explored"] | sel,
                in_v=jnp.where(sel, failure, st["in_v"]),
                w=w, h_old=h, n_old=nn,
                eps=jnp.maximum(st["eps"] * fl["epsilon_decay"],
                                fl["epsilon_min"]),
                total=st["total"] + sel.sum().astype(jnp.float32),
                rnd=st["rnd"] + 1)


# ---------------------------------------------------------------------------
# One round of the fleet simulation
# ---------------------------------------------------------------------------

def cache_interval(feats_np: dict, uses_cache: bool, n: int) -> np.ndarray:
    """Steps between cache snapshots (§4.2, from battery and stability)."""
    if not uses_cache:
        return np.full(n, BIG, np.int32)
    s = np.clip((2.0 * feats_np["battery"] * feats_np["stability"])
                .astype(np.float32), np.float32(0.5), np.float32(5.0))
    return np.clip(np.round(np.float32(2.0) * s), 1, 4).astype(np.int32)


def _round_fn(spec: dict, step_dyn, n: int):
    sim, fl, policy = spec["sim"], spec["fl"], spec["policy"]
    max_steps = int(sim["local_steps"])
    X = int(fl["cohort_size"])
    deadline = float(sim["round_deadline"])
    d_cmp = np.float32(deadline)
    d_flag = d_cmp if float(d_cmp) <= deadline \
        else np.nextafter(d_cmp, np.float32(-np.inf))
    waits = policy == "flude"
    discount = float(fl["staleness_discount"])

    def round_fn(carry, rnd, rng, dyn_base, feats, consts, hints,
                 cache_every):
        slot, pst, progress, stamp = carry
        rng, k_sel = jax.random.split(rng)
        slot, draw = step_dyn(feats, consts, rnd, slot,
                              jax.random.fold_in(dyn_base, rnd))
        online = draw.online
        if policy == "flude":
            sel, dist, resume, quorum, dstate = flude_plan(
                pst, stamp, online, k_sel, hints, fl)
            extra_w = jnp.ones((n,), jnp.float32)
        elif policy == "mifa":
            sel = online
            resume = sel & (stamp >= 0)
            dist = sel & ~resume
            stale = jnp.where(resume, jnp.maximum(rnd - stamp, 0), 0)
            extra_w = jnp.power(1.0 + stale.astype(jnp.float32), discount)
            quorum = sel.sum().astype(jnp.float32)
            dstate = None
        else:
            raise ValueError(f"the reference has no policy {policy!r}")
        idx = jnp.flatnonzero(sel, size=X, fill_value=n)

        def take(a, fill):
            return jnp.take(a, idx, axis=0, mode="fill", fill_value=fill)

        sel_x, dist_x, res_x = take(sel, False), take(dist, False), \
            take(resume, False)
        prog_x, stamp_x = take(progress, 0.0), take(stamp, -1)
        prior = jnp.round(prog_x * max_steps).astype(jnp.int32)
        steps = jnp.where(res_x, jnp.maximum(max_steps - prior, 1),
                          max_steps)
        steps = jnp.where(sel_x, steps, 0).astype(jnp.int32)
        fail_p, fail_u = take(draw.fail_p, 0.0), take(draw.fail_u, 1.0)
        w = jnp.clip(steps / max(max_steps, 1), 0.0, 1.0)
        fail = (fail_u < 1.0 - jnp.power(1.0 - fail_p, w)) & sel_x
        stop_u = take(draw.stop_u, 0.0)
        stop = jnp.where(fail, jnp.floor(stop_u * jnp.maximum(steps, 1))
                         .astype(jnp.int32), BIG)
        done = jnp.minimum(jnp.minimum(steps, stop), max_steps)
        ce = jnp.maximum(take(cache_every, 1), 1)
        cached_steps = (done // ce) * ce
        success = sel_x & ~fail & (steps > 0)
        completed = jnp.minimum(steps, stop)
        comm = float(sim["model_mb"]) * 8.0 / take(draw.bandwidth, 1.0)
        t = jnp.where(dist_x, comm, 0.0) \
            + completed / take(feats["steps_per_sec"], 1.0) \
            + jnp.where(success, comm, 0.0)
        times = jnp.where(success, t, jnp.inf)
        # quorum cut (Algorithm 2 lines 13-16)
        q = jnp.ceil(quorum).astype(jnp.int32)
        order = jnp.sort(times)
        finite = jnp.isfinite(times).sum()
        has_q = (finite >= q) & (q > 0)
        t_raw = jnp.where(has_q, order[jnp.clip(q - 1, 0, X - 1)], jnp.inf)
        if not waits:
            t_last = order[jnp.clip(finite - 1, 0, X - 1)]
            t_raw = jnp.where(~has_q & (finite > 0), t_last, t_raw)
        capped = t_raw > d_flag
        t_cut = jnp.where(capped, d_cmp, t_raw)
        recv_x = success & (times <= t_cut)
        received = jnp.zeros((n,), bool).at[idx].set(recv_x, mode="drop")
        # server-side cache bookkeeping (C3): failed devices with cached
        # progress are written, received devices cleared
        total_cached = jnp.where(res_x, prior, 0) + cached_steps
        write = sel_x & fail & (total_cached > 0)
        base_round = jnp.where(res_x & (stamp_x >= 0), stamp_x, rnd)
        tgt = jnp.where(write, idx, n)
        progress = progress.at[tgt].set(
            (total_cached / max(max_steps, 1)).astype(jnp.float32),
            mode="drop")
        stamp = stamp.at[tgt].set(base_round.astype(jnp.int32), mode="drop")
        tgt = jnp.where(recv_x, idx, n)
        progress = progress.at[tgt].set(0.0, mode="drop")
        stamp = stamp.at[tgt].set(-1, mode="drop")
        if policy == "flude":
            pst = flude_update(pst, sel, received, dstate, fl)
        stale_x = jnp.where(res_x & (stamp_x >= 0),
                            jnp.maximum(rnd - stamp_x, 0), 0) \
            .astype(jnp.float32)
        out = dict(selected=sel.sum(), received=recv_x.sum(),
                   downloads=(dist & online).sum(), capped=capped,
                   t_cut=t_cut, completed=jnp.where(sel_x, done, 0).sum(), idx=idx, sel_x=sel_x, res_x=res_x, recv_x=recv_x,
                   steps=steps, stop=stop, ce=ce, write=write,
                   stale_x=stale_x, extra_x=take(extra_w, 0.0),
                   sel=sel, received_n=received)
        return (slot, pst, progress, stamp), rng, out

    return round_fn


# ---------------------------------------------------------------------------
# Local training and aggregation (the first rounds)
# ---------------------------------------------------------------------------

def _train_fn(spec: dict, dtype):
    sim, model = spec["sim"], spec["model"]
    code = spec["model_code"]
    max_steps = int(sim["local_steps"])
    lr = float(sim["lr"])
    grad = jax.vmap(jax.value_and_grad(
        lambda p, f, x, y: code.loss_fn(p, f, x, y, model)),
        in_axes=(0, None, 0, 0))

    @jax.jit
    def train(start, frozen, x, y, steps, stop, ce):
        """SGD from ``start`` (trainable leaves (X, ...)) on each
        client's rows, the ``frozen`` leaves held fixed."""
        n = x.shape[1]
        b = min(int(sim["batch_size"]), n)
        x = _to_ref(x, dtype)

        def body(carry, j):
            params, cache, loss_sum = carry
            sl = (j * b + jnp.arange(b)) % n
            loss, g = grad(params, frozen, x[:, sl], y[:, sl])
            active = (j < steps) & (j < stop)

            def upd(p, gg):
                m = active.reshape((-1,) + (1,) * (p.ndim - 1))
                return jnp.where(m, (p - lr * gg).astype(dtype), p)

            params = jax.tree.map(upd, params, g)
            snap = active & (((j + 1) % ce) == 0)
            cache = jax.tree.map(
                lambda c, p: jnp.where(
                    snap.reshape((-1,) + (1,) * (p.ndim - 1)), p, c),
                cache, params)
            loss_sum = loss_sum + jnp.where(active, loss, 0.0) \
                .astype(jnp.float32)
            return (params, cache, loss_sum), None

        start = jax.tree.map(lambda a: a.astype(dtype), start)
        init = (start, start, jnp.zeros((x.shape[0],), jnp.float32))
        (params, cache, loss_sum), _ = jax.lax.scan(
            body, init, jnp.arange(max_steps))
        done = jnp.minimum(jnp.minimum(steps, stop), max_steps)
        return params, cache, loss_sum / jnp.maximum(done, 1)

    return train


def weiszfeld(u, w, iters: int, eps: float):
    """Smoothed Weiszfeld geometric median from the weighted mean."""
    def wsum(beta):
        return jnp.dot(beta / jnp.maximum(beta.sum(), TINY), u,
                       precision=HIGHEST)

    z = wsum(w)
    for _ in range(int(iters)):
        dist = jnp.sqrt(jnp.sum((u - z[None]) ** 2, axis=1))
        z = wsum(jnp.where(w > 0, w / jnp.maximum(dist, eps), 0.0))
    return z


def _aggregate_fn(spec: dict, mal_scale: Optional[float]):
    fl = spec["fl"]
    rule = fl.get("agg_rule", "mean")
    rp = dict(fl.get("agg_rule_params", {}))
    discount = float(fl["staleness_discount"])

    @jax.jit
    def aggregate(gvec, rows, recv, stale, extra, mal, keep):
        """New global (D,) from the cohort's (X, D) uploads."""
        w = recv.astype(jnp.float32) * float(spec["data"]["n_per_client"])
        if discount > 0.0:
            w = w * jnp.power(1.0 + jnp.maximum(stale, 0.0), -discount)
        w = w * extra * keep
        u = rows.astype(jnp.float32)
        if mal_scale is not None:
            u = jnp.where(mal[:, None], gvec[None] + mal_scale
                          * (u - gvec[None]), u)
        if rule == "mean":
            agg = jnp.dot(w / jnp.maximum(w.sum(), TINY), u,
                          precision=HIGHEST)
        elif rule == "geometric_median":
            agg = weiszfeld(u, w, int(rp.get("iters", 6)),
                            float(rp.get("eps", 1e-6)))
        else:
            raise ValueError(f"the reference has no aggregation rule "
                             f"{rule!r}")
        return jnp.where(w.sum() > 0, agg, gvec)

    return aggregate


# ---------------------------------------------------------------------------
# The whole run
# ---------------------------------------------------------------------------

def simulate(spec: dict, data, seed: int, rounds: int,
             numeric_rounds: int = 3, dtype=jnp.float32,
             fault: Optional[str] = None) -> dict:
    """Reference outputs of ``rounds`` rounds from ``seed``.

    ``spec`` holds the configuration's ``sim``, ``fl``, ``model``,
    ``data`` and ``policy`` blocks, its model file as ``model_code``,
    and the traffic's ``dynamics`` and adversary.  Returns per-round
    History columns for every round, the first rounds' masks and mean
    local losses, the global model's trainable leaves after each of the
    first ``numeric_rounds`` rounds (packed, float32), the cache
    metadata after the last round, and the cached rows after
    ``numeric_rounds`` rounds."""
    sim = dict(spec["sim"], seed=int(seed))
    fl, model, code = spec["fl"], spec["model"], spec["model_code"]
    spec = dict(spec, sim=sim)
    n = int(sim["num_clients"])
    shapes = code.leaf_shapes(model)
    names = list(shapes)
    prof = fleet_profile(sim)
    feats = {k: jnp.asarray(np.asarray(v, np.float32))
             for k, v in prof.items()}
    uses_cache = spec["policy"] in ("flude", "mifa")
    cache_every = jnp.asarray(cache_interval(prof, uses_cache, n))
    hints = jnp.asarray(np.asarray(prof["battery"] * prof["stability"],
                                   np.float32))
    adv = spec.get("adversary")
    mal_np = np.zeros(n, bool)
    mal_scale = None
    if adv:
        mal_np = malicious_mask(n, seed, float(adv["malicious_frac"]))
        mal_scale = float(adv["delta_scale"])
    mal = jnp.asarray(mal_np)

    consts_dyn, init_dyn, step_dyn = make_process(spec, n)
    consts = consts_dyn(feats)
    round_fn = jax.jit(_round_fn(spec, step_dyn, n))
    key = jax.random.key(int(seed))
    dyn_base = jax.random.fold_in(key, 0x0F1EE7)
    slot = jax.jit(init_dyn)(feats, consts,
                             jax.random.fold_in(dyn_base, 1 << 20))
    pst = flude_init(fl, n) if spec["policy"] == "flude" else None
    carry = (slot, pst, jnp.zeros((n,), jnp.float32),
             jnp.full((n,), -1, jnp.int32))

    train = _train_fn(spec, dtype)
    aggregate = _aggregate_fn(spec, mal_scale)
    trainable, frozen = code.init_params(seed, model)
    frozen = jax.tree.map(lambda a: _to_ref(a, dtype), frozen)
    theta0 = pack({k: v[None] for k, v in trainable.items()}, names)[0]
    gvec = theta0.astype(dtype).astype(jnp.float32)
    store: Dict[int, np.ndarray] = {}
    D = int(gvec.shape[0])
    globals_, losses, first = [], [], []
    outs = []
    resumed = 0
    rng = key
    for rnd in range(rounds):
        carry, rng, out = round_fn(carry, jnp.int32(rnd), rng, dyn_base,
                                   feats, consts, hints, cache_every)
        outs.append({k: out[k] for k in ("selected", "received",
                                          "downloads", "capped", "t_cut",
                                          "completed")})
        if rnd >= numeric_rounds:
            continue
        o = jax.device_get(out)
        idx = o["idx"]
        valid = idx < n
        rows = np.zeros((idx.shape[0], D), np.float32)
        resumed += int(np.sum(valid & o["res_x"]))
        for k in np.flatnonzero(valid & o["res_x"]):
            rows[k] = store.get(int(idx[k]), 0.0)
        g = unpack(gvec.astype(dtype), shapes)
        cached = unpack(jnp.asarray(rows, dtype), shapes)
        res = jnp.asarray(o["res_x"])
        start = {nm: jnp.where(
            res.reshape((-1,) + (1,) * len(shapes[nm])), cached[nm],
            g[nm][None]) for nm in names}
        xs = jnp.take(data.x, jnp.asarray(idx), axis=0, mode="fill",
                      fill_value=0)
        ys = jnp.take(data.y, jnp.asarray(idx), axis=0, mode="fill",
                      fill_value=0)
        final, cache_p, loss = train(start, frozen, xs, ys,
                                     jnp.asarray(o["steps"]),
                                     jnp.asarray(o["stop"]),
                                     jnp.asarray(o["ce"]))
        recv = o["recv_x"]
        keep = np.ones(recv.shape, np.float32)
        if fault == "half_batch":
            got = np.flatnonzero(recv)
            keep[got[len(got) // 2:]] = 0.0
        gvec = aggregate(gvec.astype(jnp.float32), pack(final, names), recv,
                         o["stale_x"], o["extra_x"],
                         jnp.take(mal, jnp.asarray(idx), mode="fill",
                                  fill_value=False), jnp.asarray(keep))
        gvec = gvec.astype(dtype).astype(jnp.float32)
        cache_rows = np.asarray(pack(cache_p, names), np.float32)
        for k in range(idx.shape[0]):
            cid = int(idx[k])
            if cid >= n:
                continue
            if o["write"][k]:
                store[cid] = cache_rows[k].copy()
            elif recv[k]:
                store.pop(cid, None)
        sel_x = o["sel_x"]
        losses.append(float(np.asarray(loss)[sel_x].sum()
                            / max(int(sel_x.sum()), 1)))
        globals_.append(np.asarray(gvec, np.float32))
        first.append(dict(selected=o["sel"], received=o["received_n"]))
        if rnd == numeric_rounds - 1:
            cache_after = {int(c): r for c, r in store.items()}
    if rounds < numeric_rounds:
        cache_after = {int(c): r for c, r in store.items()}
    cols = jax.device_get({k: jnp.stack([o[k] for o in outs])
                           for k in outs[0]}) if outs else {}
    hist = [{k: v[r] for k, v in cols.items()} for r in range(len(outs))]
    cum_comm = cum_time = 0.0
    wall, comm = [], []
    for o in hist:
        cum_comm += (int(o["downloads"]) + int(o["received"])) \
            * float(sim["model_mb"])
        cum_time += float(sim["round_deadline"]) if bool(o["capped"]) \
            else float(o["t_cut"])
        wall.append(cum_time)
        comm.append(cum_comm)
    _, pst, progress, stamp = carry
    return dict(
        selected=[int(o["selected"]) for o in hist],
        received=[int(o["received"]) for o in hist],
        wall_clock=wall, comm_mb=comm,
        completed_steps=[int(o["completed"]) for o in hist],
        first=first, losses=losses, globals=globals_,
        theta0=np.asarray(theta0),
        progress=np.asarray(progress), stamp=np.asarray(stamp),
        part_count=None if pst is None else np.asarray(pst["part"]),
        cache_after=cache_after, resumed=resumed, leaf_sizes=[int(np.prod(shapes[k]))
                                             for k in names],
        leaf_names=names)
