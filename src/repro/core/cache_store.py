"""Host-offloaded C3 cache store (``FLConfig.cache_offload``).

Under ``cache_offload="host"`` the fleet's (N, D) C3 cache params no
longer live on device: the device keeps only the (N,) cache *metadata*
(progress, round stamp — everything planning reads) plus the current
cohort's (X, D) slot block, and this module owns the host side of that
round trip:

* :class:`HostCacheStore` — a sparse per-client row store (one entry per
  client that actually holds a cached model), so host memory tracks the
  number of *live* cache slots, not the enrolled fleet.  A fetch of a
  never-written (or sentinel-padded, or cleared) row reads as the empty
  slot — zero params — which is exactly what the resident pytree's
  gather produces for rows whose metadata says "no cache", so the jitted
  round body needs no special handling.
* :class:`CohortCacheStream` — the async double-buffering protocol
  around the store.  Written slots stream back with
  ``copy_to_host_async`` immediately after the server step is
  *dispatched* and are drained one round later, when the next fetch
  needs them; the next cohort's slots are gathered and shipped with an
  async ``jax.device_put`` as soon as the cohort index is known.  No
  O(X·D) copy ever blocks the round that produced it — the only
  blocking reads are on handles whose device-to-host copies were issued
  a full dispatch earlier (counted in the stream's own
  :class:`TransferStats`, exposed as ``FleetEngine.transfer_stats`` —
  counters are strictly per-engine; the old process-wide ``STATS``
  aggregate is gone, and ``repro.analysis.lint`` rejects the pattern).

``cache_offload="discard"`` additionally drops rows whose round stamp is
more than ``cache_staleness_bound`` rounds old (the paper's cache is
best-effort — §4.2 — so expiry is a legal memory/accuracy knob).  The
matching device-side metadata expiry lives in
``repro.core.caching.expire_caches`` and runs *before* planning each
round with the same bound, so the planner never resumes a pruned row.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.obs.trace import NULL_TRACER


@dataclasses.dataclass
class TransferStats:
    """Per-stream counters of the offload stream's host transfers.

    ``*_async`` count *dispatches* of asynchronous copies (one per
    pytree, not per leaf); ``pre_issued_reads`` counts blocking
    ``np.asarray`` reads on handles whose device-to-host copy was
    already issued a dispatch earlier (the double-buffering drain);
    ``sync_copies`` counts synchronous round-blocking copies — the
    streaming protocol never performs one, and the transfer-count tests
    assert it stays zero.
    """
    h2d_async: int = 0
    d2h_async: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    pre_issued_reads: int = 0
    sync_copies: int = 0

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


def _tree_bytes(tree) -> int:
    return sum(int(np.asarray(l).nbytes) for l in jax.tree.leaves(tree))


class HostCacheStore:
    """Sparse host-side store of per-client C3 cache rows.

    One entry per client id that currently holds a cached local model;
    each entry is the flattened list of per-leaf numpy rows (owned
    copies — never views into a transient cohort block) plus the round
    stamp the row was written with.  ``num_clients`` is the sentinel id:
    gathers treat it (and any never-written id) as the empty slot.
    """

    def __init__(self, template_params, num_clients: int,
                 staleness_bound: Optional[int] = None):
        leaves, treedef = jax.tree.flatten(template_params)
        self._treedef = treedef
        self._shapes = [tuple(np.shape(l)) for l in leaves]
        self._dtypes = [np.asarray(l).dtype for l in leaves]
        self.num_clients = int(num_clients)
        self.staleness_bound = None if staleness_bound is None \
            else int(staleness_bound)
        self.row_bytes = sum(
            int(np.prod(s, dtype=np.int64)) * d.itemsize
            for s, d in zip(self._shapes, self._dtypes))
        self._rows: Dict[int, List[np.ndarray]] = {}
        self._stamps: Dict[int, int] = {}

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        return len(self._rows)

    @property
    def nbytes(self) -> int:
        """Live host bytes of stored cache rows (excludes dict overhead)."""
        return len(self._rows) * self.row_bytes

    def stamp_of(self, client_id: int) -> Optional[int]:
        return self._stamps.get(int(client_id))

    def clear(self) -> None:
        self._rows.clear()
        self._stamps.clear()

    # -- fetch / apply ------------------------------------------------------

    def gather(self, idx: np.ndarray) -> Any:
        """Stacked (X, ...) host pytree of the rows at ``idx``.

        Sentinel ids (``num_clients``) and ids with no stored row read as
        zeros — the empty-slot value the resident pytree's gather
        produces for the same rows.  (Rows whose device metadata was
        *cleared* keep their stale buffer in the resident pytree but
        read as zeros here; nothing consumes either value — resume is
        False wherever the metadata says "no cache" — so round outputs
        are identical.)
        """
        idx = np.asarray(idx)
        x = idx.shape[0]
        out = [np.zeros((x,) + s, d)
               for s, d in zip(self._shapes, self._dtypes)]
        for k in range(x):
            row = self._rows.get(int(idx[k]))
            if row is not None:
                for j, leaf in enumerate(row):
                    out[j][k] = leaf
        return jax.tree.unflatten(self._treedef, out)

    def apply(self, idx: np.ndarray, write: np.ndarray, clear: np.ndarray,
              stamps: np.ndarray, block, current_round: int) -> None:
        """Apply one round's cache bookkeeping to the store.

        ``idx``/``write``/``clear``/``stamps`` are (X,) host arrays;
        ``block`` is the (X, ...) cohort cache-params pytree the trainer
        produced.  Rows are written where ``write`` (owned copies),
        deleted where ``clear`` (a received upload invalidates the slot
        — the host row becomes unreachable because the device metadata
        is reset, so keeping it would only leak memory).  ``write`` and
        ``clear`` are disjoint by construction (fail vs success).
        Under a staleness bound, rows older than the bound at
        ``current_round`` are pruned — mirroring the device-side
        ``expire_caches`` metadata expiry, which runs with the same
        bound before this round's plan, so no pruned row can be fetched
        as a resume.
        """
        idx = np.asarray(idx)
        write = np.asarray(write)
        clear = np.asarray(clear)
        stamps = np.asarray(stamps)
        leaves = [np.asarray(l) for l in jax.tree.leaves(block)]
        n = self.num_clients
        for k in range(idx.shape[0]):
            cid = int(idx[k])
            if cid >= n:
                continue
            if write[k]:
                self._rows[cid] = [np.array(l[k]) for l in leaves]
                self._stamps[cid] = int(stamps[k])
            elif clear[k]:
                self._rows.pop(cid, None)
                self._stamps.pop(cid, None)
        if self.staleness_bound is not None:
            self.prune(current_round)

    def prune(self, current_round: int) -> None:
        """Drop rows staler than the bound at ``current_round``."""
        bound = self.staleness_bound
        if bound is None:
            return
        dead = [cid for cid, st in self._stamps.items()
                if int(current_round) - st > bound]
        for cid in dead:
            self._rows.pop(cid, None)
            self._stamps.pop(cid, None)


class CohortCacheStream:
    """Double-buffered device↔host streaming of cohort cache slots.

    The engine drives it with two calls per round:

    * ``fetch(idx, rnd)`` — called as soon as the round's cohort index
      is dispatched.  Starts the async device-to-host copy of ``idx``,
      drains the *previous* round's staged write-back (whose async
      copies have been in flight since that round's server step was
      dispatched), gathers the cohort's rows from the store and ships
      them back with an async ``jax.device_put`` onto the cohort
      sharding.
    * ``stage(idx, write, clear, block, stamps)`` — called right after
      the server step is dispatched.  Starts ``copy_to_host_async`` on
      every handle and parks them; nothing blocks until the next
      round's ``fetch`` (or ``flush``) reads them.

    ``tracer`` (the engine hands over its run's tracer, and
    ``NULL_TRACER`` when none) spans each step of the protocol as a
    child of the engine's ``cache_fetch``/``cache_stage``/
    ``cache_flush`` span: ``cache_d2h_issue``, ``cache_count_bytes``,
    ``cache_drain``, ``cache_read``, ``cache_apply``, ``cache_gather``
    and ``cache_put``.
    """

    def __init__(self, store: HostCacheStore, mesh=None,
                 cohort_size: Optional[int] = None,
                 stats: Optional[TransferStats] = None):
        self.store = store
        self.mesh = mesh
        self.cohort_size = cohort_size
        # per-stream counters (the engine passes its own instance)
        self.stats = stats if stats is not None else TransferStats()
        self.tracer = NULL_TRACER
        self._pending = None

    def _sharding(self, tree):
        if self.mesh is None:
            return None
        from repro.sharding import partitioning as SP
        return jax.tree.map(
            lambda l: SP.cohort_sharding(self.mesh, np.asarray(l).ndim),
            tree)

    def _start_d2h(self, tree) -> None:
        with self.tracer.span("cache_d2h_issue"):
            for leaf in jax.tree.leaves(tree):
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
        self.stats.d2h_async += 1
        with self.tracer.span("cache_count_bytes"):
            self.stats.d2h_bytes += _tree_bytes(tree)

    def _read(self, tree):
        """Blocking read of handles whose copy was pre-issued."""
        self.stats.pre_issued_reads += 1
        with self.tracer.span("cache_read"):
            return jax.tree.map(np.asarray, tree)

    def fetch(self, idx, rnd: int):
        """(X, ...) device block of the cohort's cache rows (async put)."""
        self._start_d2h(idx)           # overlap with draining the pending
        self.drain(rnd)
        idx_np = self._read(idx)
        with self.tracer.span("cache_gather"):
            block = self.store.gather(idx_np)
        sh = self._sharding(block)
        with self.tracer.span("cache_put"):
            put = jax.device_put(block) if sh is None \
                else jax.device_put(block, sh)
        self.stats.h2d_async += 1
        self.stats.h2d_bytes += _tree_bytes(block)
        return put

    def stage(self, idx, write, clear, block, stamps) -> None:
        """Park one round's cache write-back; copies start now."""
        self.drain()                   # at most one round in flight
        payload = (idx, write, clear, stamps, block)
        self._start_d2h(payload)
        self._pending = payload

    def drain(self, rnd: Optional[int] = None) -> None:
        """Apply the parked write-back (blocks on pre-issued copies)."""
        if self._pending is None:
            return
        with self.tracer.span("cache_drain"):
            idx, write, clear, stamps, block = self._read(self._pending)
            self._pending = None
            with self.tracer.span("cache_apply"):
                self.store.apply(idx, write, clear, stamps, block,
                                 0 if rnd is None else int(rnd))

    def flush(self, rnd: Optional[int] = None) -> None:
        self.drain(rnd)

    def reset(self) -> None:
        self._pending = None
        self.store.clear()
