"""Device metrics registry: per-round scalars fused into one dispatch.

Each ``@register_metric`` entry is a pure jnp reduction over the round's
device-resident context (plan masks, receive mask, losses, finish times,
cache metadata, the stacked trainer output and the pre-step global
model).  ``make_metrics_fn`` selects the metrics whose declared needs
the engine's active round path can supply at the configured level and
fuses them into a *single* jitted dispatch whose outputs are device
scalars (or small fixed-size vectors) — the engine pushes the handles
through the round ledger, so metric values ride the existing pipelined
readback and add **zero** per-round host syncs.  With
``FLConfig.telemetry=None`` the factory is never called and the round
path is bit-for-bit (and dispatch-count) identical to an uninstrumented
engine.

Context keys (the engine supplies the subset its path produces; every
per-client array is the (N,) fleet view, ``rows``/``rows_mask`` are the
stacked trainer rows — (N, ...) full scan or (X, ...) cohort block):

``selected, distribute, resume, online, received, fail`` — (N,) bool
masks; ``losses`` — (N,) mean local loss; ``times`` — (N,) finish
times (inf = no upload); ``progress, stamp`` — (N,) C3 cache metadata
*before* the server step (post plan-side expiry); ``stamp_pre_expire``
— (N,) stamps before the discard-mode expiry (discard runs only);
``rule_state`` — (N,) robust-aggregation state (stateful rules);
``rows, rows_mask, global`` — stacked client params, their receive
mask, and the pre-step global model; ``rnd`` — the round index;
``expert_load`` — (experts_held,) pairs the held experts of an
expert-parallel MoE model took (cohort paths of such a model only).

Static keys (``make_metrics_fn(static=...)``): ``num_clients``,
``cohort_size`` (None on the full scan), ``local_steps``,
``staleness_edges``, and optionally ``rows_bound`` — the round's
static selection bound, letting O(rows · D) metrics gather the
received rows into a compact block before reducing (the full-scan
``rows`` view is fleet-sized).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.sharding import partitioning as SP

LEVELS = ("basic", "full")
_RANK = {lvl: i for i, lvl in enumerate(LEVELS)}

# default staleness-histogram bucket edges (rounds since cache write);
# bucket b counts edges[b] <= staleness < edges[b+1], last bucket open
STALENESS_EDGES = (0, 1, 2, 4, 8, 16)


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    level: str
    needs: Tuple[str, ...]           # ctx keys (+ static availability
    fn: Callable                     # flags like "cohort_size")


_REGISTRY: Dict[str, MetricSpec] = {}


def register_metric(name: str, *, level: str = "basic",
                    needs: Sequence[str] = (),
                    allow_override: bool = False):
    """Register ``fn(ctx, static) -> {column: device scalar/vector}``.

    ``level`` gates when the metric compiles in (``"basic"`` runs at
    both levels, ``"full"`` only at full); ``needs`` lists the context
    keys the reduction reads — the engine's round path advertises what
    it can supply and metrics with unmet needs are skipped, never
    traced.
    """
    if level not in LEVELS:
        raise ValueError(f"metric level must be one of {LEVELS}, got "
                         f"{level!r}")

    def deco(fn):
        if name in _REGISTRY and not allow_override:
            raise ValueError(f"metric {name!r} already registered")
        _REGISTRY[name] = MetricSpec(name, level, tuple(needs), fn)
        return fn

    return deco


def available_metrics():
    return sorted(_REGISTRY)


def metrics_for(level: str, available) -> Tuple[MetricSpec, ...]:
    """Registered metrics active at ``level`` whose needs ``available``
    (a set of ctx keys + static availability flags) satisfies."""
    if level not in LEVELS:
        raise ValueError(f"telemetry level must be one of {LEVELS}, got "
                         f"{level!r}")
    avail = set(available)
    return tuple(s for _, s in sorted(_REGISTRY.items())
                 if _RANK[s.level] <= _RANK[level]
                 and set(s.needs) <= avail)


def make_metrics_fn(level: str, available, static: dict, mesh=None):
    """Fuse the active metrics into one jitted dispatch.

    Returns ``(fn, needed)``: ``fn(ctx) -> {column: device value}`` and
    the tuple of ctx keys the engine must supply (the union of the
    selected metrics' needs, minus static flags).  Returns
    ``(None, ())`` when no metric applies.
    """
    specs = metrics_for(level, available)
    if not specs:
        return None, ()
    needed = tuple(sorted({k for s in specs for k in s.needs
                           if k not in static}))

    @jax.jit
    def metrics_fn(ctx):
        out = {}
        for spec in specs:
            vals = spec.fn(ctx, static)
            dup = set(vals) & set(out)
            if dup:
                raise ValueError(f"metric {spec.name!r} re-emits "
                                 f"columns {sorted(dup)}")
            out.update(vals)
        # metric outputs are replicated reductions — pin that under the
        # client mesh so readback never gathers
        return SP.replicated_constraint(out, mesh)

    return metrics_fn, needed


# ---------------------------------------------------------------------------
# Masked-reduction helpers (shared numpy-oracle-friendly definitions)
# ---------------------------------------------------------------------------

def _count(mask):
    return jnp.sum(mask.astype(jnp.int32))


def _masked_mean_max(values, mask):
    """Mean/max of ``values`` over ``mask`` rows (0.0 when empty)."""
    n = jnp.sum(mask.astype(values.dtype))
    got = jnp.where(mask, values, 0.0)
    return jnp.sum(got) / jnp.maximum(n, 1.0), jnp.max(got)


# ---------------------------------------------------------------------------
# Built-in metrics
# ---------------------------------------------------------------------------

@register_metric("counts", needs=("selected", "received", "fail",
                                  "online", "distribute"))
def _counts(ctx, static):
    """Fleet participation counters (Alg. 2 accounting)."""
    return {
        "selected_count": _count(ctx["selected"]),
        "received_count": _count(ctx["received"]),
        "interrupted_count": _count(ctx["fail"]),
        "online_count": _count(ctx["online"]),
        "download_count": _count(ctx["distribute"] & ctx["online"]),
    }


@register_metric("local_loss", needs=("losses", "received"))
def _local_loss(ctx, static):
    """Mean/max local training loss over the uploads the server saw."""
    mean, mx = _masked_mean_max(ctx["losses"], ctx["received"])
    return {"local_loss_mean": mean, "local_loss_max": mx}


@register_metric("round_time", needs=("times", "received"))
def _round_time(ctx, static):
    """Mean/max finish time of received uploads (why was it slow?)."""
    mean, mx = _masked_mean_max(ctx["times"], ctx["received"])
    return {"finish_time_mean": mean, "finish_time_max": mx}


@register_metric("cache", needs=("stamp", "resume", "selected"))
def _cache(ctx, static):
    """C3 cache residency + hits (resumed-from-cache selections)."""
    return {
        "cache_rows": _count(ctx["stamp"] >= 0),
        "cache_hit_count": _count(ctx["resume"] & ctx["selected"]),
    }


@register_metric("expert_load", needs=("expert_load",))
def _expert_load(ctx, static):
    """Held-expert load of an expert-parallel MoE model: (token, slot)
    pairs routed to this chip's experts, summed over its MoE layers, for
    the cohort block's first local batch through the round's starting
    model (empty cohort slots train, and count, too), and the
    most-loaded held expert's share of them."""
    load = ctx["expert_load"].astype(jnp.float32)
    total = jnp.sum(load)
    return {"moe_routed_pairs": total,
            "moe_max_expert_share": jnp.max(load) / jnp.maximum(total, 1.0)}


@register_metric("cohort_fill", needs=("selected", "cohort_size"))
def _cohort_fill(ctx, static):
    """Fraction of the static (X,) cohort block the round used."""
    x = static["cohort_size"]
    return {"cohort_fill": _count(ctx["selected"]) / jnp.float32(x)}


@register_metric("cache_expired", level="full",
                 needs=("stamp", "stamp_pre_expire"))
def _cache_expired(ctx, static):
    """Rows the discard-mode staleness bound pruned this round."""
    dead = (ctx["stamp_pre_expire"] >= 0) & (ctx["stamp"] < 0)
    return {"cache_expired_count": _count(dead)}


@register_metric("staleness_hist", level="full", needs=("stamp", "rnd"))
def _staleness_hist(ctx, static):
    """Histogram of live cache-row staleness (rounds since write)."""
    edges = static["staleness_edges"]
    stamp = ctx["stamp"]
    live = stamp >= 0
    s = ctx["rnd"] - stamp
    buckets = []
    for b, lo in enumerate(edges):
        hi = edges[b + 1] if b + 1 < len(edges) else None
        m = live & (s >= lo)
        if hi is not None:
            m = m & (s < hi)
        buckets.append(_count(m))
    return {"staleness_hist": jnp.stack(buckets)}


@register_metric("trust_quantiles", level="full", needs=("rule_state",))
def _trust_quantiles(ctx, static):
    """Quartiles + extremes of the per-client robust-rule trust state."""
    state = ctx["rule_state"].astype(jnp.float32)
    q = jnp.quantile(state, jnp.array([0.25, 0.5, 0.75], jnp.float32))
    return {"trust_quartiles": q,
            "trust_min": jnp.min(state), "trust_max": jnp.max(state)}


@register_metric("update_norm", level="full",
                 needs=("rows", "rows_mask", "global"))
def _update_norm(ctx, static):
    """Per-upload delta-norm stats and their residual around the plain
    received-mean delta (dispersion the robust rules act on).

    This is the one metric whose input is O(rows · D), so it keeps the
    reductions off the fleet-sized stack: when the engine advertises
    ``rows_bound`` (the round's static selection bound) below the
    fleet view's leading dim, the received rows are first gathered
    into a compact (K, ...) block — the full-scan path then reads K
    rows instead of all N.  The residual around the received-mean row
    expands as ``||d - m||² = ||d||² - 2⟨d, m⟩ + ||m||²`` so each
    leaf's delta block is built once and every reduction is a fused
    product over it."""
    rows, mask = ctx["rows"], ctx["rows_mask"]
    g = ctx["global"]
    lead = jax.tree.leaves(rows)[0].shape[0]
    bound = static.get("rows_bound")
    if bound is not None and bound < lead:
        idx = jnp.flatnonzero(mask, size=bound, fill_value=lead)
        rows = jax.tree.map(
            lambda r: jnp.take(r, jnp.minimum(idx, lead - 1), axis=0),
            rows)
        mask = idx < lead
    cnt = jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)
    maskf = mask.astype(jnp.float32)
    sq, dots, msq = 0.0, 0.0, 0.0
    for r, gl in zip(jax.tree.leaves(rows), jax.tree.leaves(g)):
        d = (r - gl).reshape(r.shape[0], -1).astype(jnp.float32)
        sq = sq + jnp.einsum("nd,nd->n", d, d)
        md = maskf @ d / cnt                   # masked mean delta m
        dots = dots + d @ md
        msq = msq + jnp.sum(md * md)
    norms = jnp.sqrt(sq)
    n_mean, n_max = _masked_mean_max(norms, mask)
    resid = jnp.sqrt(jnp.maximum(sq - 2.0 * dots + msq, 0.0))
    r_mean, r_max = _masked_mean_max(resid, mask)
    return {"update_norm_mean": n_mean, "update_norm_max": n_max,
            "agg_residual_mean": r_mean, "agg_residual_max": r_max}
