"""C3 — local model caching (paper §4.2).

Each device keeps a *rolling single-slot* cache of its latest local training
state (model params, progress fraction, round stamp).  When an interrupted
device rejoins, it resumes from the cache unless the server's staleness-aware
distributor (C4) overrides it with a fresh global model.

In cross-device mode the fleet's caches are a leading-axis-stacked pytree
(N_clients first dim on every leaf) so cache update/resume are pure
``jnp.where`` ops that shard over the client mesh axes.  In cross-silo mode
(huge models) only the metadata (progress, round stamp) is kept — see
DESIGN.md §3 hardware adaptation.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp


class ClientCaches(NamedTuple):
    params: Any              # pytree, each leaf (N, ...) — cached local state
    progress: jax.Array      # (N,) float32 in [0,1] — fraction completed
    round_stamp: jax.Array   # (N,) int32 — round when cached (-1 = empty)


def init_caches(template_params, num_clients: int) -> ClientCaches:
    stacked = jax.tree.map(
        lambda a: jnp.zeros((num_clients,) + a.shape, a.dtype),
        template_params)
    return ClientCaches(
        stacked,
        jnp.zeros((num_clients,), jnp.float32),
        jnp.full((num_clients,), -1, jnp.int32))


def reset_caches(caches: ClientCaches) -> ClientCaches:
    """Value-identical to :func:`init_caches`, but shaped for buffer
    recycling: jitted with ``donate_argnums=0`` the zero/-1 fills write
    into the donated leaves in place, so a fresh run on a long-lived
    engine memsets the existing (N, ...) fleet buffers instead of
    faulting in a new cache pytree (at N=4096 the fresh allocation is
    ~7x the memset)."""
    return ClientCaches(
        jax.tree.map(jnp.zeros_like, caches.params),
        jnp.zeros_like(caches.progress),
        jnp.full_like(caches.round_stamp, -1))


def write_cache(caches: ClientCaches, mask: jax.Array, new_params,
                progress: jax.Array, rnd) -> ClientCaches:
    """Rolling update: overwrite the slot for masked clients (latest only).

    new_params leaves are (N, ...) stacked local states.
    """
    def upd(old, new):
        m = mask.reshape((-1,) + (1,) * (old.ndim - 1))
        return jnp.where(m, new.astype(old.dtype), old)

    return ClientCaches(
        jax.tree.map(upd, caches.params, new_params),
        jnp.where(mask, progress, caches.progress),
        jnp.where(mask, jnp.asarray(rnd, jnp.int32), caches.round_stamp))


def clear_cache(caches: ClientCaches, mask: jax.Array) -> ClientCaches:
    """After a successful upload the local cache slot is invalidated."""
    return ClientCaches(
        caches.params,
        jnp.where(mask, 0.0, caches.progress),
        jnp.where(mask, -1, caches.round_stamp))


# ---------------------------------------------------------------------------
# Compact cohorts: gather (N,) slots into dense (X,) blocks and scatter back
# ---------------------------------------------------------------------------
#
# The cohort index is an ascending (X,) int array of selected client ids,
# padded with the out-of-range sentinel N (``repro.fl.api.cohort_index``).
# Gathers use ``mode="fill"`` so sentinel rows read as *empty* slots;
# scatters predicate their row mask into the index (unwritten rows point at
# the sentinel) and drop out-of-range writes — together a gather→update→
# scatter round trip equals the full-fleet ``jnp.where`` update exactly.

def take_rows(a, idx, fill):
    """Rows ``idx`` of ``a`` along dim 0, sentinel rows reading ``fill``;
    ``idx=None`` is the identity (every row, in order)."""
    if idx is None:
        return a
    return jnp.take(a, idx, axis=0, mode="fill", fill_value=fill)


def gather_caches(caches: ClientCaches, idx: jax.Array) -> ClientCaches:
    """Dense (X, ...) view of the cache slots at ``idx``.

    Sentinel (padding) rows read as empty: zero params, zero progress,
    round stamp -1 — the same values an untouched fresh slot holds, so
    downstream resume/staleness logic needs no special pad handling.
    ``idx=None`` is the identity (every row, in order).
    """
    if idx is None:
        return caches
    return ClientCaches(
        jax.tree.map(lambda a: take_rows(a, idx, 0), caches.params),
        take_rows(caches.progress, idx, 0.0),
        take_rows(caches.round_stamp, idx, -1))


def scatter_write_cache(caches: ClientCaches, idx: jax.Array,
                        mask: jax.Array, new_params,
                        progress: jax.Array, rnd) -> ClientCaches:
    """:func:`write_cache` restricted to the cohort rows ``idx``.

    ``mask``/``new_params``/``progress``/``rnd`` are (X,)-leading cohort
    arrays.  Masked-off rows are redirected to the sentinel and dropped,
    so every unwritten (N,) slot keeps its existing buffer — equal to the
    full-fleet rolling ``jnp.where`` update when the full write mask is
    zero outside the cohort (which it is: writes require selection).
    ``idx=None`` is :func:`write_cache` itself.
    """
    if idx is None:
        return write_cache(caches, mask, new_params, progress, rnd)
    n = caches.progress.shape[0]
    target = jnp.where(mask, idx, n)

    def upd(old, new):
        return old.at[target].set(new.astype(old.dtype), mode="drop")

    return ClientCaches(
        jax.tree.map(upd, caches.params, new_params),
        caches.progress.at[target].set(
            progress.astype(jnp.float32), mode="drop"),
        caches.round_stamp.at[target].set(
            jnp.asarray(rnd, jnp.int32), mode="drop"))


def scatter_clear_cache(caches: ClientCaches, idx: jax.Array,
                        mask: jax.Array) -> ClientCaches:
    """:func:`clear_cache` restricted to the cohort rows ``idx`` (params
    stay, metadata resets — identical to the full-fleet clear for masks
    that are zero outside the cohort).  ``idx=None`` is
    :func:`clear_cache` itself."""
    if idx is None:
        return clear_cache(caches, mask)
    n = caches.progress.shape[0]
    target = jnp.where(mask, idx, n)
    return ClientCaches(
        caches.params,
        caches.progress.at[target].set(0.0, mode="drop"),
        caches.round_stamp.at[target].set(-1, mode="drop"))


def expire_caches(caches: ClientCaches, current_round,
                  staleness_bound: int) -> ClientCaches:
    """Drop cache slots staler than ``staleness_bound`` rounds.

    The device half of ``FLConfig.cache_offload="discard"``: metadata of
    rows whose stamp is more than ``staleness_bound`` rounds old resets
    to the empty slot (progress 0, stamp -1) *before* planning reads it,
    so the planner consistently sees the slot as absent and never
    schedules a resume the host store has pruned.  Params leaves pass
    through untouched — in offload mode there are none on device, and
    an unreachable resident row is dead weight either way.
    """
    stale = (jnp.asarray(current_round, jnp.int32) - caches.round_stamp) \
        > staleness_bound
    return ClientCaches(
        caches.params,
        jnp.where(stale, 0.0, caches.progress),
        jnp.where(stale, -1, caches.round_stamp))


def staleness(caches: ClientCaches, current_round) -> jax.Array:
    """Rounds elapsed since the cache was written (∞-ish if empty)."""
    empty = caches.round_stamp < 0
    s = (jnp.asarray(current_round, jnp.int32) - caches.round_stamp)
    return jnp.where(empty, jnp.int32(1 << 20), s).astype(jnp.float32)


def has_cache(caches: ClientCaches) -> jax.Array:
    return caches.round_stamp >= 0


def resume_params(caches: ClientCaches, global_params, use_cache_mask):
    """Per-client starting state: cached params where resuming, else the
    fresh global model (broadcast).  Leaves: (N, ...)."""
    def pick(cached, g):
        m = use_cache_mask.reshape((-1,) + (1,) * (cached.ndim - 1))
        return jnp.where(m, cached, g[None].astype(cached.dtype))

    return jax.tree.map(pick, caches.params, global_params)


def adaptive_cache_interval(base_interval, battery: jax.Array,
                            stability: jax.Array) -> jax.Array:
    """§4.2 "adjusting caching frequency": lower battery / flakier network
    ⇒ cache more often (smaller interval); stable+charged ⇒ less often.

    battery, stability ∈ [0, 1].  Returns per-device seconds, clamped to
    [base/2, 5·base] (paper's examples: 30 s … 5 min around a 1-min base).
    """
    scale = jnp.clip(2.0 * battery * stability, 0.5, 5.0)
    return base_interval * scale
