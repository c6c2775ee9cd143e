"""Host-offloaded C3 cache store (``FLConfig.cache_offload``).

Under ``cache_offload="host"`` the fleet's (N, D) C3 cache params no
longer live on device: the device keeps only the (N,) cache *metadata*
(progress, round stamp — everything planning reads) plus the current
cohort's (X, D) slot block, and this module owns the host side of that
round trip:

* :class:`HostCacheStore` — a per-client row store in slab memory it
  allocates a chunk at a time and reuses (across runs too), so host
  memory tracks the high-water mark of *live* cache slots, not the
  enrolled fleet, and steady rounds allocate nothing.  A fetch of a
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
  a full dispatch earlier (counted in the :class:`TransferStats` the
  stream shares with its store, exposed as ``FleetEngine.transfer_stats`` —
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
from typing import Any, List, Optional

import jax
import numpy as np

from repro.obs.trace import NULL_TRACER

# Rows are picked out of a block whose cohort axis is innermost (the
# TPU's layout for the trainer's (X, ...) cache block) a tile of ~4 MB
# of the block at a time, so that the tile stays in cache meanwhile.
_TILE_BYTES = 4 << 20


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

    The host store's own counters: ``rows_written`` rows copied into
    the store, ``rows_cleared`` rows it released (a received upload's
    clear or a staleness prune), ``rows_hit`` rows the stream's fetch
    served from the store (the live part of each (X, ...) block), and
    ``host_grows`` slab chunks the store allocated — zero once the
    store has reached its live-row high-water mark.
    """
    h2d_async: int = 0
    d2h_async: int = 0
    h2d_bytes: int = 0
    d2h_bytes: int = 0
    pre_issued_reads: int = 0
    sync_copies: int = 0
    rows_written: int = 0
    rows_cleared: int = 0
    rows_hit: int = 0
    host_grows: int = 0

    def reset(self) -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, 0)

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


def _tree_bytes(tree) -> int:
    return sum(int(np.asarray(l).nbytes) for l in jax.tree.leaves(tree))


def _pick_rows(src_t: np.ndarray, ks: np.ndarray, dst) -> None:
    """Rows ``ks`` of ``src_t`` (a block with its cohort axis moved
    last, C-contiguous) into the slab rows ``dst`` (``(slab leaf, rows,
    positions in ks)`` per chunk), a cache-sized tile of the leading
    axis at a time, reading the block in its memory order."""
    n = src_t.shape[0]
    tile = max(1, _TILE_BYTES // max(src_t[0].nbytes, 1))
    for a in range(0, n, tile):
        b = min(a + tile, n)
        picked = np.moveaxis(np.take(src_t[a:b], ks, axis=-1), -1, 0)
        for slab, rs, js in dst:
            slab[rs, a:b] = picked[js]


class HostCacheStore:
    """Host-side store of per-client C3 cache rows in reused slab memory.

    Rows live in per-leaf slabs of shape (chunk, *leaf_shape), allocated
    a chunk at a time; ``chunk`` is the row count of the first applied
    block.  An (N + 1,) slot map sends each client id to its slab slot
    (-1: no row; entry N is the sentinel, always -1), freed slots go on
    a free list, and ``clear`` empties the map but keeps the slabs, so
    host memory stays at the live-row high-water mark and a run that
    repeats an earlier one allocates nothing.  ``num_clients`` is the
    sentinel id: gathers treat it (and any never-written or unknown
    id) as the empty slot.
    """

    def __init__(self, template_params, num_clients: int,
                 staleness_bound: Optional[int] = None,
                 stats: Optional[TransferStats] = None):
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
        # per-store counters (the engine passes its own instance)
        self.stats = stats if stats is not None else TransferStats()
        self._chunk = 0                         # rows per slab chunk
        self._slabs: List[List[np.ndarray]] = []    # [chunk][leaf]
        self._free: List[int] = []              # free slots, popped last
        self._slot = np.full(self.num_clients + 1, -1, np.int64)
        self._stamp = np.zeros(self.num_clients, np.int64)

    # -- introspection ------------------------------------------------------

    def __len__(self) -> int:
        return self._chunk * len(self._slabs) - len(self._free)

    @property
    def nbytes(self) -> int:
        """Live host bytes of stored cache rows."""
        return len(self) * self.row_bytes

    @property
    def capacity_bytes(self) -> int:
        """Host bytes of the slabs: the live-row high-water mark,
        rounded up to a chunk (kept across ``clear``)."""
        return self._chunk * len(self._slabs) * self.row_bytes

    def ids(self) -> List[int]:
        """Client ids that hold a row, ascending."""
        return np.flatnonzero(self._slot[:-1] >= 0).tolist()

    def stamp_of(self, client_id: int) -> Optional[int]:
        cid = int(client_id)
        if not 0 <= cid < self.num_clients or self._slot[cid] < 0:
            return None
        return int(self._stamp[cid])

    def clear(self) -> None:
        """Drop every row; the slabs stay allocated for the next run."""
        self._slot[:] = -1
        self._free = list(range(self._chunk * len(self._slabs) - 1, -1, -1))

    # -- slots --------------------------------------------------------------

    def _slots_of(self, idx: np.ndarray) -> np.ndarray:
        """Slot of each id (-1: sentinel, unknown or no row)."""
        n = self.num_clients
        return self._slot[np.where((idx >= 0) & (idx < n), idx, n)]

    def _locate(self, slot: int):
        """(per-leaf slab chunk, row in it) of ``slot``."""
        c, r = divmod(slot, self._chunk)
        return self._slabs[c], r

    def _take_slot(self) -> int:
        if not self._free:
            base = self._chunk * len(self._slabs)
            self._slabs.append([np.empty((self._chunk,) + s, d)
                                for s, d in zip(self._shapes, self._dtypes)])
            self._free = list(range(base + self._chunk - 1, base - 1, -1))
            self.stats.host_grows += 1
        return self._free.pop()

    def _release(self, cids: np.ndarray) -> None:
        slots = self._slot[cids]
        live = slots >= 0
        self._free.extend(slots[live].tolist())
        self._slot[cids] = -1
        self.stats.rows_cleared += int(live.sum())

    def _write_rows(self, leaves: List[np.ndarray], ks: np.ndarray,
                    slots: np.ndarray) -> None:
        """Copy rows ``ks`` of the (X, ...) ``leaves`` into ``slots``.

        A leaf stored row by row is copied a row at a time.  A leaf that
        stores its cohort axis innermost has strided rows (one cache
        line holds 16 rows' float32s, and a row copy reads a line per
        element): it is read in memory order instead (``_pick_rows``),
        so each line is read once.
        """
        if not ks.size:
            return
        chunk, row = np.divmod(slots, self._chunk)
        groups = [(c, row[chunk == c], np.flatnonzero(chunk == c))
                  for c in np.unique(chunk).tolist()]
        for j, src in enumerate(leaves):
            src_t = np.moveaxis(src, 0, -1)
            if src.flags.c_contiguous or not src_t.flags.c_contiguous:
                for k, slot in zip(ks.tolist(), slots.tolist()):
                    slab, r = self._locate(slot)
                    slab[j][r] = src[k]
            else:
                _pick_rows(src_t, ks, [(self._slabs[c][j], rs, js)
                                       for c, rs, js in groups])

    def _new_block(self, x: int) -> List[np.ndarray]:
        return [np.zeros((x,) + s, d)
                for s, d in zip(self._shapes, self._dtypes)]

    def _copy_rows(self, idx: np.ndarray, out: List[np.ndarray]
                   ) -> np.ndarray:
        """Copy the stored rows of ``idx`` into the matching rows of the
        (X, ...) leaves ``out``; rows without a stored row are left
        alone.  Returns the row positions that were filled."""
        slots = self._slots_of(idx)
        hit = np.flatnonzero(slots >= 0)
        for k, slot in zip(hit.tolist(), slots[hit].tolist()):
            slab, r = self._locate(slot)
            for dst, src in zip(out, slab):
                dst[k] = src[r]
        return hit

    # -- fetch / apply ------------------------------------------------------

    def gather(self, idx: np.ndarray) -> Any:
        """Stacked (X, ...) host pytree of the rows at ``idx``, in fresh
        arrays the caller owns.

        Sentinel ids (``num_clients``) and ids with no stored row read as
        zeros — the empty-slot value the resident pytree's gather
        produces for the same rows.  (Rows whose device metadata was
        *cleared* keep their stale buffer in the resident pytree but
        read as zeros here; nothing consumes either value — resume is
        False wherever the metadata says "no cache" — so round outputs
        are identical.)
        """
        idx = np.asarray(idx)
        out = self._new_block(idx.shape[0])
        self._copy_rows(idx, out)
        return jax.tree.unflatten(self._treedef, out)

    def apply(self, idx: np.ndarray, write: np.ndarray, clear: np.ndarray,
              stamps: np.ndarray, block, current_round: int) -> None:
        """Apply one round's cache bookkeeping to the store.

        ``idx``/``write``/``clear``/``stamps`` are (X,) host arrays;
        ``block`` is the (X, ...) cohort cache-params pytree the trainer
        produced.  Rows are copied into their slots where ``write``,
        released where ``clear`` (a received upload invalidates the slot
        — the host row becomes unreachable because the device metadata
        is reset, so keeping it would only hold memory).  ``write`` and
        ``clear`` are disjoint by construction (fail vs success); where
        an id repeats, its last write or clear wins.  Under a staleness
        bound, rows older than the bound at ``current_round`` are
        pruned — mirroring the device-side ``expire_caches`` metadata
        expiry, which runs with the same bound before this round's
        plan, so no pruned row can be fetched as a resume.
        """
        idx = np.asarray(idx)
        write = np.asarray(write, bool)
        stamps = np.asarray(stamps)
        if not self._chunk:
            self._chunk = max(int(idx.shape[0]), 1)
        # the last write or clear of each known id decides its row
        act = np.flatnonzero((idx >= 0) & (idx < self.num_clients)
                             & (write | np.asarray(clear, bool)))[::-1]
        _, first = np.unique(idx[act], return_index=True)
        last = act[first]
        self._release(idx[last[~write[last]]])
        wrote = last[write[last]]
        cids = idx[wrote]
        for cid in cids[self._slot[cids] < 0].tolist():
            self._slot[cid] = self._take_slot()
        self._stamp[cids] = stamps[wrote]
        self._write_rows([np.asarray(l) for l in jax.tree.leaves(block)],
                         wrote, self._slot[cids])
        self.stats.rows_written += int(wrote.size)
        if self.staleness_bound is not None:
            self.prune(current_round)

    def prune(self, current_round: int) -> None:
        """Drop rows staler than the bound at ``current_round``."""
        bound = self.staleness_bound
        if bound is None:
            return
        dead = np.flatnonzero((self._slot[:-1] >= 0)
                              & (int(current_round) - self._stamp > bound))
        self._release(dead)


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

    The fetched rows are gathered into one of two (X, ...) staging
    blocks the stream owns, used in turn so that a block is never
    rewritten while a put from it may still be in flight; before each
    use only the rows that held hits last time are zeroed, so the block
    handed to ``device_put`` is byte-identical to a fresh
    ``store.gather(idx)`` at a cost in hits, not X.  The next-but-one
    fetch rewrites a block only after its drain has read the previous
    round's write-back, which the device produces after the trainer
    that consumed the block's put.  (On
    a backend whose ``device_put`` may alias host memory, the CPU, the
    fetched array shares its staging block: consume it before then, as
    the round does.)

    ``tracer`` (the engine hands over its run's tracer, and
    ``NULL_TRACER`` when none) spans each step of the protocol as a
    child of the engine's ``cache_fetch``/``cache_stage``/
    ``cache_flush`` span: ``cache_d2h_issue``, ``cache_count_bytes``,
    ``cache_drain``, ``cache_read``, ``cache_apply``, ``cache_gather``
    and ``cache_put``.
    """

    def __init__(self, store: HostCacheStore, mesh=None,
                 cohort_size: Optional[int] = None):
        self.store = store
        self.mesh = mesh
        self.cohort_size = cohort_size
        # the store's counters: the stream counts its transfers there
        self.stats = store.stats
        self.tracer = NULL_TRACER
        self._pending = None
        # two staging blocks, used in turn, and the rows of each that
        # hold hits
        self._blocks: List[List[np.ndarray]] = []
        self._hits: List[np.ndarray] = []

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
            block = self._gather(idx_np)
        sh = self._sharding(block)
        with self.tracer.span("cache_put"):
            put = jax.device_put(block) if sh is None \
                else jax.device_put(block, sh)
        self.stats.h2d_async += 1
        self.stats.h2d_bytes += _tree_bytes(block)
        return put

    def _gather(self, idx: np.ndarray):
        """``store.gather(idx)`` into the next staging block."""
        x = idx.shape[0]
        if not self._blocks or self._blocks[0][0].shape[0] != x:
            self._blocks = [self.store._new_block(x) for _ in range(2)]
            self._hits = [np.empty(0, int)] * 2
        self._blocks.reverse()
        self._hits.reverse()
        leaves = self._blocks[0]
        for leaf in leaves:
            leaf[self._hits[0]] = 0
        self._hits[0] = self.store._copy_rows(idx, leaves)
        self.stats.rows_hit += int(self._hits[0].size)
        return jax.tree.unflatten(self.store._treedef, leaves)

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
