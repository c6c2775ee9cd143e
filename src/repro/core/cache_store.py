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
* :class:`CohortCacheStream` — the async protocol around the store,
  which moves only live rows across the host link.  ``stage`` parks the
  round's device handles, the trainer's (X, ...) cache block among
  them, and starts async device-to-host copies of the four (X,) arrays
  only: cohort index, write and clear masks, stamps.  The next round's
  drain (its ``fetch`` runs it before the gather, so a client written in
  round r can resume in round r + 1) reads those, dispatches the
  compaction ``jit_cache_compact`` — the K written rows gathered into a
  row-major (bucket(K), ...) block — copies that block back, applies it
  and drops the (X, ...) block.  ``fetch`` puts only the H rows the
  store holds for the cohort, as a row-major (bucket(H), ...) block,
  with their (bucket(H),) positions (padding points past the cohort);
  the expansion ``jit_cache_expand`` scatters them into a zero (X, ...)
  block on the cohort sharding: the trainer's input, equal to the full
  block the store would gather.  A round with no hit puts positions
  only, a round with no write copies back nothing.  Buckets are the
  multiples of max(8, ⌈X/16⌉) rows (rounded to the mesh's device count)
  up to X, at most 16 sizes each way, all compiled at the stream's
  first fetch, so a run's later rounds and later runs compile nothing.
  No copy ever blocks the round that produced it: the blocking reads
  are the next round's, on copies issued beforehand (counted in the
  :class:`TransferStats` the stream shares with its store, exposed as
  ``FleetEngine.transfer_stats`` — counters are strictly per-engine;
  the old process-wide ``STATS`` aggregate is gone, and
  ``repro.analysis.lint`` rejects the pattern).

``cache_offload="discard"`` additionally drops rows whose round stamp is
more than ``cache_staleness_bound`` rounds old (the paper's cache is
best-effort — §4.2 — so expiry is a legal memory/accuracy knob).  The
matching device-side metadata expiry lives in
``repro.core.caching.expire_caches`` and runs *before* planning each
round with the same bound, so the planner never resumes a pruned row.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout
from jax.sharding import SingleDeviceSharding

from repro.obs.trace import NULL_TRACER


@dataclasses.dataclass
class TransferStats:
    """Per-stream counters of the offload stream's host transfers.

    ``*_async`` count *dispatches* of asynchronous copies (one per
    pytree, not per leaf): a round puts one block of hit rows and their
    positions, and copies back its cohort index, its four (X,) arrays
    and, when it wrote rows, one compacted block.  ``pre_issued_reads``
    counts blocking ``np.asarray`` reads on handles whose
    device-to-host copy was issued before the read — every copy is read
    once, in the next round's drain or fetch; ``sync_copies`` counts
    synchronous round-blocking copies — the streaming protocol never
    performs one, and the transfer-count tests assert it stays zero.
    ``*_bytes`` count what crossed the link, from shapes; ``h2d_rows``
    and ``d2h_rows`` the cache rows that crossed, bucket padding
    included.

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
    h2d_rows: int = 0
    d2h_rows: int = 0
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
    """Bytes of a pytree's leaves, from their shapes and dtypes alone:
    no leaf is read."""
    return sum(math.prod(l.shape) * np.dtype(l.dtype).itemsize
               for l in jax.tree.leaves(tree))


def row_buckets(x: int, devices: int = 1) -> List[int]:
    """Row counts the stream rounds a block of up to ``x`` rows up to:
    the multiples of max(8, ⌈x/16⌉), rounded up to a multiple of
    ``devices`` (a mesh shards every bucket evenly), capped at ``x`` —
    at most 16 sizes."""
    step = max(8, -(-x // 16))
    step = -(-step // devices) * devices
    return sorted({min(step * j, x) for j in range(1, -(-x // step) + 1)})


def _bucket(buckets: List[int], n: int) -> int:
    return 0 if n == 0 else buckets[bisect.bisect_left(buckets, n)]


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
        """Copy rows ``ks`` of the leaves ``leaves`` into ``slots``, a
        row at a time (rows of any layout; the stream's compacted
        blocks arrive row-major, so each row is one contiguous copy)."""
        for k, slot in zip(ks.tolist(), slots.tolist()):
            slab, r = self._locate(slot)
            for dst, src in zip(slab, leaves):
                dst[r] = src[k]

    def _new_block(self, x: int) -> List[np.ndarray]:
        return [np.zeros((x,) + s, d)
                for s, d in zip(self._shapes, self._dtypes)]

    def _copy_rows(self, slots: np.ndarray, out: List[np.ndarray],
                   at) -> None:
        """Copy the rows in ``slots`` (all live) into rows ``at`` of the
        leaves ``out``."""
        for k, slot in zip(at, slots.tolist()):
            slab, r = self._locate(slot)
            for dst, src in zip(out, slab):
                dst[k] = src[r]

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
        slots = self._slots_of(idx)
        hit = np.flatnonzero(slots >= 0)
        self._copy_rows(slots[hit], out, hit.tolist())
        return jax.tree.unflatten(self._treedef, out)

    def apply(self, idx: np.ndarray, write: np.ndarray, clear: np.ndarray,
              stamps: np.ndarray, block, current_round: int,
              rows: Optional[np.ndarray] = None) -> None:
        """Apply one round's cache bookkeeping to the store.

        ``idx``/``write``/``clear``/``stamps`` are (X,) host arrays;
        ``block`` is the (X, ...) cohort cache-params pytree the trainer
        produced or, where ``rows`` is given, a block whose leading rows
        are the rows at those (ascending) positions of ``idx`` — the
        stream's compacted write-back, which holds every written row.
        Rows are copied into their slots where ``write``,
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
        src = wrote if rows is None else np.searchsorted(rows, wrote)
        self._write_rows([np.asarray(l) for l in jax.tree.leaves(block)],
                         src, self._slot[cids])
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


def _row_major(ndim: int, sharding) -> Format:
    """Format of a block whose rows are contiguous (cohort axis
    outermost), so that its host copy is C-contiguous: the TPU's default
    layout may put the cohort axis innermost."""
    return Format(Layout(major_to_minor=tuple(range(ndim))), sharding)


class CohortCacheStream:
    """Device↔host streaming of the live rows of cohort cache slots.

    The engine drives it with two calls per round:

    * ``fetch(idx, rnd)`` — called as soon as the round's cohort index
      is dispatched.  Starts the async device-to-host copy of ``idx``,
      drains the *previous* round's staged write-back, copies the
      cohort's stored rows into a (bucket(H), ...) staging block, puts
      it with the rows' positions in one async ``jax.device_put`` and
      returns the (X, ...) block ``jit_cache_expand`` builds from them.
    * ``stage(idx, write, clear, block, stamps)`` — called right after
      the server step is dispatched.  Parks the handles and starts the
      async copies of the four (X,) arrays; it reads nothing, and the
      (X, ...) ``block`` stays on the device until the next drain
      compacts its written rows (``jit_cache_compact``).

    Hit rows are copied into the leading rows of one of two (X, ...)
    staging blocks the stream owns, used in turn, so that a block is
    never rewritten while a put from it may still be in flight: the
    next-but-one fetch rewrites a block only after its drain has read
    the previous round's masks, which the device produces after the
    expansion that consumed the block's put.  (On a backend whose
    ``device_put`` may alias host memory, the CPU, the put shares its
    staging block.)

    ``tracer`` (the engine hands over its run's tracer, and
    ``NULL_TRACER`` when none) spans each step of the protocol as a
    child of the engine's ``cache_fetch``/``cache_stage``/
    ``cache_flush`` span: ``cache_d2h_issue``, ``cache_drain``,
    ``cache_read``, ``cache_compact``, ``cache_apply``,
    ``cache_gather``, ``cache_put`` and ``cache_expand``.
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
        # per cohort size X: the buckets, the staging blocks and the
        # compiled expansion and compaction of every bucket
        self._x = 0
        self._buckets: List[int] = []
        self._blocks: List[List[np.ndarray]] = []
        self._put_shardings = None
        # the expansion and compactions as jitted functions (the
        # auditor lowers them) and compiled for every bucket
        self.expand_fn = None
        self.compact_fns: Dict[int, Any] = {}
        self._expand: Dict[int, Any] = {}
        self._compact: Dict[int, Any] = {}

    def _sharding(self, ndim: int):
        """The cohort sharding of an (X, ...) or bucket block; on one
        device none, so that the fetched block stays uncommitted like
        the round's other inputs (a committed one would commit every
        output downstream and recompile the round's programs)."""
        if self.mesh is None:
            return None
        from repro.sharding import partitioning as SP
        return SP.cohort_sharding(self.mesh, ndim)

    def _size_for(self, x: int) -> None:
        """Buckets, staging blocks, expansions and compactions for
        cohorts of ``x`` rows, every bucket compiled now (at the first
        fetch, before the first trainer)."""
        if x == self._x:
            return
        store = self.store
        devices = 1 if self.mesh is None else self.mesh.devices.size
        self._x = x
        self._buckets = row_buckets(x, devices)
        self._blocks = [store._new_block(x) for _ in range(2)]
        ndims = [1 + len(s) for s in store._shapes]

        def cache_expand(rows, pos):
            return jax.tree.map(
                lambda r: jnp.zeros((x,) + r.shape[1:], r.dtype)
                .at[pos].set(r, mode="drop"), rows)

        if self.mesh is None:
            self.expand_fn = jax.jit(cache_expand)
        else:
            out = store._treedef.unflatten(
                [self._sharding(nd) for nd in ndims])
            self._put_shardings = (out, self._sharding(1))
            self.expand_fn = jax.jit(cache_expand,
                                     in_shardings=self._put_shardings,
                                     out_shardings=out)
        self._expand = {}
        for h in [0] + self._buckets:
            rows = store._treedef.unflatten(
                [jax.ShapeDtypeStruct((h,) + s, d)
                 for s, d in zip(store._shapes, store._dtypes)])
            self._expand[h] = self.expand_fn.lower(
                rows, jax.ShapeDtypeStruct((h,), jnp.int32)).compile()

        n = store.num_clients
        outs = store._treedef.unflatten(
            [_row_major(nd, self._sharding(nd)
                        or SingleDeviceSharding(jax.devices()[0]))
             for nd in ndims])

        def compaction(b):
            def cache_compact(block, write, idx):
                keep = write & (idx >= 0) & (idx < n)
                at = jnp.nonzero(keep, size=b, fill_value=0)[0]
                return jax.tree.map(lambda l: l[at], block)
            return jax.jit(cache_compact, out_shardings=outs)

        block = store._treedef.unflatten(
            [jax.ShapeDtypeStruct((x,) + s, d, sharding=self._sharding(nd))
             for s, d, nd in zip(store._shapes, store._dtypes, ndims)])
        write = jax.ShapeDtypeStruct((x,), bool, sharding=self._sharding(1))
        idx = jax.ShapeDtypeStruct((x,), jnp.int32,
                                   sharding=self._sharding(1))
        self.compact_fns = {b: compaction(b) for b in self._buckets}
        self._compact = {b: fn.lower(block, write, idx).compile()
                         for b, fn in self.compact_fns.items()}

    def _start_d2h(self, tree) -> None:
        with self.tracer.span("cache_d2h_issue"):
            for leaf in jax.tree.leaves(tree):
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
        self.stats.d2h_async += 1
        self.stats.d2h_bytes += _tree_bytes(tree)

    def _read(self, tree):
        """Blocking read of handles whose copy was issued beforehand."""
        self.stats.pre_issued_reads += 1
        with self.tracer.span("cache_read"):
            return jax.tree.map(np.asarray, tree)

    def fetch(self, idx, rnd: int):
        """(X, ...) device block of the cohort's cache rows: the stored
        rows cross the link, the expansion fills in the zeros."""
        self._start_d2h(idx)           # overlap with draining the pending
        self.drain(rnd)
        idx_np = self._read(idx)
        self._size_for(idx_np.shape[0])
        with self.tracer.span("cache_gather"):
            rows, pos = self._gather(idx_np)
        with self.tracer.span("cache_put"):
            put = jax.device_put((rows, pos), self._put_shardings)
        self.stats.h2d_async += 1
        self.stats.h2d_bytes += _tree_bytes((rows, pos))
        self.stats.h2d_rows += pos.shape[0]
        with self.tracer.span("cache_expand"):
            return self._expand[pos.shape[0]](*put)

    def _gather(self, idx: np.ndarray):
        """The stored rows of ``idx``, in the leading rows of the next
        staging block, and their (bucket,) positions (padding: X)."""
        slots = self.store._slots_of(idx)
        hit = np.flatnonzero(slots >= 0)
        h = _bucket(self._buckets, hit.size)
        self._blocks.reverse()
        leaves = [leaf[:h] for leaf in self._blocks[0]]
        self.store._copy_rows(slots[hit], leaves, range(hit.size))
        pos = np.full(h, idx.shape[0], np.int32)
        pos[:hit.size] = hit
        self.stats.rows_hit += int(hit.size)
        return jax.tree.unflatten(self.store._treedef, leaves), pos

    def stage(self, idx, write, clear, block, stamps) -> None:
        """Park one round's cache write-back; the (X,) copies start now
        and nothing is read."""
        self.drain()                   # at most one round in flight
        self._start_d2h((idx, write, clear, stamps))
        self._pending = (idx, write, clear, stamps, block)

    def drain(self, rnd: Optional[int] = None) -> None:
        """Apply the parked write-back: read its (X,) arrays, copy back
        the compacted written rows and apply them."""
        if self._pending is None:
            return
        with self.tracer.span("cache_drain"):
            idx_d, write_d, clear, stamps, block = self._pending
            self._pending = None
            idx, write, clear, stamps = self._read(
                (idx_d, write_d, clear, stamps))
            self._size_for(idx.shape[0])
            n = self.store.num_clients
            live = np.flatnonzero(write.astype(bool) & (idx >= 0)
                                  & (idx < n))
            rows = None
            if live.size:
                k = _bucket(self._buckets, live.size)
                with self.tracer.span("cache_compact"):
                    rows = self._compact[k](block, write_d, idx_d)
                self._start_d2h(rows)
                self.stats.d2h_rows += k
                rows = self._read(rows)
            del block
            with self.tracer.span("cache_apply"):
                self.store.apply(idx, write, clear, stamps, rows,
                                 0 if rnd is None else int(rnd), rows=live)

    def flush(self, rnd: Optional[int] = None) -> None:
        self.drain(rnd)

    def reset(self) -> None:
        self._pending = None
        self.store.clear()
