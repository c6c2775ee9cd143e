"""Hypothesis property tests for the host-offloaded C3 cache store.

``test_cache_store.py`` holds a seeded-random sweep of the same
round-trip invariant so coverage survives without the hypothesis
dependency; this module widens the search (arbitrary fleet sizes,
cohort widths, write/clear sequences, sentinel rows and staleness
bounds) where hypothesis is available.

The invariant under test is the store's parity contract with the
resident (N, D) pytree: after any sequence of per-round
``apply(idx, write, clear, stamps, block)`` calls, a ``gather`` reads
— for every row whose metadata says "has a live cache" — exactly the
bytes the resident pytree's ``gather_caches`` would produce, and zeros
everywhere metadata says "empty" (never-written, cleared, sentinel, or
expired under a ``"discard"`` staleness bound).  Metadata is the
arbiter on both paths, which is why the two engines run bit-identical
rounds.
"""
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.cache_store import HostCacheStore  # noqa: E402

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def _template(dim):
    return {"w": np.zeros((2, dim), np.float32),
            "b": np.zeros((dim,), np.float32)}


class _ResidentReference:
    """The resident-pytree semantics, in plain numpy: a dense (N, ...)
    buffer plus per-row stamps.  ``write`` overwrites rows, ``clear``
    resets metadata (the buffer keeps its stale bytes — exactly the
    resident engine's behavior), expiry resets metadata under a bound.
    A gather returns the buffer where the stamp is live, zeros
    elsewhere — what the jitted round body actually consumes."""

    def __init__(self, template, n, bound=None):
        self.rows = {k: np.zeros((n,) + v.shape, v.dtype)
                     for k, v in template.items()}
        self.stamp = np.full(n, -1, np.int64)
        self.n = n
        self.bound = bound

    def apply(self, idx, write, clear, stamps, block, rnd):
        for k in range(len(idx)):
            cid = int(idx[k])
            if cid >= self.n:
                continue
            if write[k]:
                for name in self.rows:
                    self.rows[name][cid] = block[name][k]
                self.stamp[cid] = int(stamps[k])
            elif clear[k]:
                self.stamp[cid] = -1
        if self.bound is not None:
            self.stamp[(rnd - self.stamp > self.bound)
                       & (self.stamp >= 0)] = -1

    def gather(self, idx):
        out = {name: np.zeros((len(idx),) + buf.shape[1:], buf.dtype)
               for name, buf in self.rows.items()}
        for k, cid in enumerate(idx):
            cid = int(cid)
            if cid < self.n and self.stamp[cid] >= 0:
                for name in self.rows:
                    out[name][k] = self.rows[name][cid]
        return out


@st.composite
def _round_sequences(draw, unique=True):
    n = draw(st.integers(2, 24))
    x = draw(st.integers(1, min(n, 8)))
    dim = draw(st.integers(1, 4))
    bound = draw(st.one_of(st.none(), st.integers(1, 4)))
    n_rounds = draw(st.integers(1, 6))
    rounds = []
    for r in range(n_rounds):
        ids = draw(st.lists(st.integers(0, n - 1), min_size=0,
                            max_size=x, unique=unique))
        idx = np.full(x, n, np.int64)          # sentinel padding
        idx[:len(ids)] = sorted(ids)
        write = np.zeros(x, bool)
        clear = np.zeros(x, bool)
        for k in range(len(ids)):
            op = draw(st.sampled_from(["write", "clear", "none"]))
            write[k] = op == "write"
            clear[k] = op == "clear"
        stamps = np.array([draw(st.integers(0, r)) for _ in range(x)],
                          np.int64)
        seed = draw(st.integers(0, 2 ** 16))
        probe = draw(st.lists(st.integers(0, n), min_size=1,
                              max_size=6))   # n itself = sentinel probe
        rounds.append((idx, write, clear, stamps, seed, probe))
    return n, x, dim, bound, rounds


@given(_round_sequences())
def test_store_roundtrip_matches_resident_reference(case):
    """evict→fetch parity: any select/write/clear sequence leaves the
    sparse store and the dense resident reference gather-identical,
    sentinel rows and staleness expiry included."""
    n, x, dim, bound, rounds = case
    template = _template(dim)
    store = HostCacheStore(template, n, staleness_bound=bound)
    ref = _ResidentReference(template, n, bound=bound)
    for rnd, (idx, write, clear, stamps, seed, probe) in enumerate(rounds):
        rng = np.random.default_rng(seed)
        block = {name: rng.normal(size=(x,) + v.shape).astype(v.dtype)
                 for name, v in template.items()}
        store.apply(idx, write, clear, stamps, block, rnd)
        ref.apply(idx, write, clear, stamps, block, rnd)
        got = store.gather(np.asarray(probe))
        want = ref.gather(np.asarray(probe))
        for name in template:
            np.testing.assert_array_equal(got[name], want[name],
                                          err_msg=f"round {rnd} {name}")
    # live-row accounting: writes/clears (and, under a bound, the shared
    # prune predicate) keep the sparse store and the reference's live
    # stamps in lockstep
    assert len(store) == int((ref.stamp >= 0).sum())


def _cohort_minor(block):
    """The same (X, ...) values, stored with the cohort axis innermost
    (the layout a TPU gives the trainer's cache block)."""
    return {name: np.moveaxis(np.ascontiguousarray(np.moveaxis(v, 0, -1)),
                              -1, 0) for name, v in block.items()}


@given(_round_sequences(unique=False), st.integers(0, 6),
       st.lists(st.booleans(), min_size=6, max_size=6))
def test_slab_store_matches_resident_reference(case, clear_at, minor):
    """The slab store against the dense reference with ids repeated
    inside a round (the last write or clear wins), sentinels and
    prune, blocks stored row by row or cohort axis innermost, and a
    ``clear()`` part way (capacity kept, both restart empty): gathers,
    ``ids()``, ``stamp_of`` and the live-row count agree after every
    round."""
    n, x, dim, bound, rounds = case
    template = _template(dim)
    store = HostCacheStore(template, n, staleness_bound=bound)
    ref = _ResidentReference(template, n, bound=bound)
    for rnd, (idx, write, clear, stamps, seed, probe) in enumerate(rounds):
        if rnd == clear_at:
            cap = store.capacity_bytes
            store.clear()
            ref = _ResidentReference(template, n, bound=bound)
            assert store.capacity_bytes == cap and len(store) == 0
        rng = np.random.default_rng(seed)
        block = {name: rng.normal(size=(x,) + v.shape).astype(v.dtype)
                 for name, v in template.items()}
        store.apply(idx, write, clear, stamps,
                    _cohort_minor(block) if minor[rnd] else block, rnd)
        ref.apply(idx, write, clear, stamps, block, rnd)
        got = store.gather(np.asarray(probe))
        want = ref.gather(np.asarray(probe))
        for name in template:
            np.testing.assert_array_equal(got[name], want[name],
                                          err_msg=f"round {rnd} {name}")
        live = np.flatnonzero(ref.stamp >= 0)
        assert store.ids() == live.tolist()
        assert [store.stamp_of(c) for c in live] == ref.stamp[live].tolist()
        assert store.nbytes == len(live) * store.row_bytes \
            <= store.capacity_bytes


@given(st.integers(2, 16), st.integers(1, 6), st.integers(0, 2 ** 16))
def test_store_rows_are_owned_copies(n, dim, seed):
    """Mutating the staged block after ``apply`` never changes what a
    later ``gather`` reads — rows are copies, not views."""
    template = _template(dim)
    store = HostCacheStore(template, n)
    rng = np.random.default_rng(seed)
    block = {name: rng.normal(size=(1,) + v.shape).astype(v.dtype)
             for name, v in template.items()}
    keep = {name: v.copy() for name, v in block.items()}
    store.apply(np.array([0]), np.array([True]), np.array([False]),
                np.array([3]), block, 3)
    for v in block.values():
        v[:] = np.inf
    got = store.gather(np.array([0]))
    for name in template:
        np.testing.assert_array_equal(got[name][0], keep[name][0])
