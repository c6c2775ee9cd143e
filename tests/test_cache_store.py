"""Host-offloaded C3 cache store (``FLConfig.cache_offload``).

Covers, on a single device (the sharded variant is the slow subprocess
test at the bottom):

* config validation of the offload knobs;
* ``HostCacheStore`` semantics — slab rows, empty-slot gathers,
  write/clear/prune bookkeeping, owned-copy rows;
* offload-vs-resident golden parity: every registered policy, padded
  cohorts, pipelined depths, repeated runs on one engine, the stateful
  robust rule and ``"discard"`` with a bound the run never crosses —
  bit-identical ``History``;
* the streaming contract: zero synchronous round-blocking copies, O(1)
  async copies per round, per-round host transfers independent of the
  round count (and of N — the stream only ever moves (X, ...) blocks);
* ``server_step_memory`` reporting the device/host cache residency
  split (device O(X·D) under offload) and the agg-rule state bytes;
* ``"discard"`` staleness semantics on the live store.

The hypothesis round-trip property tests live in
``test_cache_store_properties.py``.
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.configs.base import FLConfig
from repro.core import cache_store as CS
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, SimConfig, available_policies

N = 32
SIM = SimConfig(num_clients=N, rounds=3, local_steps=2, batch_size=8,
                seed=3)
FL = FLConfig(num_clients=N, clients_per_round=8, dynamics="markov",
              cohort_size=8)


@pytest.fixture(scope="module")
def data():
    return federated_classification(N, seed=4, n_per_client=16)


def _run(data, fl, policy, **kw):
    return FleetEngine(data, SIM, fl).run(policy, diagnostics=False, **kw)


def _assert_hist_equal(a, b, ctx=""):
    """Bitwise History equality — the offload path's exactness contract."""
    for f in ("acc", "comm_mb", "wall_clock", "received", "selected"):
        assert getattr(a, f) == getattr(b, f), (ctx, f)


def _template():
    return {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.zeros(4, np.float32)}


# ---------------------------------------------------------------------------
# Config validation
# ---------------------------------------------------------------------------

def test_cache_offload_rejects_unknown_mode():
    with pytest.raises(ValueError, match="cache_offload"):
        FLConfig(num_clients=N, cohort_size=8, cache_offload="disk")


def test_cache_offload_requires_cohort():
    with pytest.raises(ValueError, match="requires cohort_size"):
        FLConfig(num_clients=N, cache_offload="host")


@pytest.mark.parametrize("bad", [0, -3, True, 1.5])
def test_staleness_bound_rejects_non_positive(bad):
    with pytest.raises(ValueError, match="cache_staleness_bound"):
        FLConfig(num_clients=N, cohort_size=8, cache_offload="discard",
                 cache_staleness_bound=bad)


# ---------------------------------------------------------------------------
# HostCacheStore semantics
# ---------------------------------------------------------------------------

def test_store_empty_gather_is_zero():
    store = CS.HostCacheStore(_template(), num_clients=8)
    got = store.gather(np.array([0, 3, 8]))      # 8 = sentinel
    assert got["w"].shape == (3, 2, 3)
    assert not got["w"].any() and not got["b"].any()
    assert len(store) == 0 and store.nbytes == 0


def test_store_write_fetch_clear_roundtrip():
    store = CS.HostCacheStore(_template(), num_clients=8)
    block = {"w": np.random.default_rng(0).normal(size=(3, 2, 3))
             .astype(np.float32),
             "b": np.ones((3, 4), np.float32)}
    idx = np.array([1, 4, 8])                    # last row is the sentinel
    store.apply(idx, write=np.array([True, True, True]),
                clear=np.zeros(3, bool), stamps=np.array([0, 0, 0]),
                block=block, current_round=0)
    assert len(store) == 2                       # sentinel write dropped
    assert store.nbytes == 2 * store.row_bytes
    got = store.gather(np.array([4, 1, 2]))
    np.testing.assert_array_equal(got["w"][0], block["w"][1])
    np.testing.assert_array_equal(got["w"][1], block["w"][0])
    assert not got["w"][2].any()                 # never-written row
    # rows are owned copies, not views into the transient block
    block["w"][:] = -1.0
    np.testing.assert_array_equal(store.gather(np.array([1]))["w"][0]
                                  .ravel()[:1] == -1.0, [False])
    store.apply(np.array([1]), write=np.array([False]),
                clear=np.array([True]), stamps=np.array([0]),
                block={"w": np.zeros((1, 2, 3), np.float32),
                       "b": np.zeros((1, 4), np.float32)},
                current_round=1)
    assert len(store) == 1 and store.stamp_of(1) is None


def test_store_prune_drops_stale_rows():
    store = CS.HostCacheStore(_template(), num_clients=8,
                              staleness_bound=2)
    block = {"w": np.ones((2, 2, 3), np.float32),
             "b": np.ones((2, 4), np.float32)}
    store.apply(np.array([0, 5]), write=np.array([True, True]),
                clear=np.zeros(2, bool), stamps=np.array([0, 3]),
                block=block, current_round=2)   # 2-0 <= 2: both survive
    assert len(store) == 2
    store.prune(5)           # 5 - 0 > 2 drops row 0; 5 - 3 <= 2 keeps 5
    assert len(store) == 1 and store.stamp_of(0) is None
    assert store.stamp_of(5) == 3


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_store_roundtrip_random_sequences(seed):
    """Seeded sweep of the round-trip invariant (the hypothesis version
    lives in ``test_cache_store_properties.py``): after any sequence of
    applies, a gather reads the resident-reference bytes wherever the
    metadata says "live cache" and zeros everywhere else — sentinel
    rows, cleared rows and bound-expired rows included."""
    rng = np.random.default_rng(seed)
    n, x = int(rng.integers(4, 20)), int(rng.integers(1, 8))
    bound = None if seed % 2 else int(rng.integers(1, 4))
    template = _template()
    store = CS.HostCacheStore(template, n, staleness_bound=bound)
    ref_rows = {k: np.zeros((n,) + v.shape, v.dtype)
                for k, v in template.items()}
    ref_stamp = np.full(n, -1, np.int64)
    for rnd in range(6):
        ids = rng.choice(n, size=min(x, n), replace=False)
        k_live = int(rng.integers(0, len(ids) + 1))
        idx = np.full(x, n, np.int64)
        idx[:k_live] = np.sort(ids[:k_live])
        op = rng.integers(0, 3, size=x)          # 0 write, 1 clear, 2 no-op
        write, clear = op == 0, op == 1
        stamps = rng.integers(0, rnd + 1, size=x)
        block = {k: rng.normal(size=(x,) + v.shape).astype(v.dtype)
                 for k, v in template.items()}
        store.apply(idx, write, clear, stamps, block, rnd)
        for k in range(x):
            cid = int(idx[k])
            if cid >= n:
                continue
            if write[k]:
                for name in ref_rows:
                    ref_rows[name][cid] = block[name][k]
                ref_stamp[cid] = stamps[k]
            elif clear[k]:
                ref_stamp[cid] = -1
        if bound is not None:
            ref_stamp[(rnd - ref_stamp > bound) & (ref_stamp >= 0)] = -1
        probe = rng.integers(0, n + 1, size=5)   # n = sentinel probe
        got = store.gather(probe)
        for name in ref_rows:
            for k, cid in enumerate(probe):
                cid = int(cid)
                want = ref_rows[name][cid] \
                    if cid < n and ref_stamp[cid] >= 0 \
                    else np.zeros_like(ref_rows[name][0])
                np.testing.assert_array_equal(got[name][k], want,
                                              err_msg=f"r{rnd} {name}")
    assert len(store) == int((ref_stamp >= 0).sum())


def _block(rng, template, x):
    return {k: rng.normal(size=(x,) + v.shape).astype(v.dtype)
            for k, v in template.items()}


def test_store_clear_keeps_capacity_and_reuses_slots():
    """Slabs grow a chunk (the first block's X) at a time; slots freed
    by a clear, a prune or ``clear()`` are reused before the store
    grows again, and ``clear()`` keeps the slabs."""
    rng = np.random.default_rng(0)
    template = _template()
    store = CS.HostCacheStore(template, num_clients=16, staleness_bound=2)
    ones, none = np.ones(4, bool), np.zeros(4, bool)
    store.apply(np.array([0, 1, 2, 3]), ones, none, np.zeros(4, int),
                _block(rng, template, 4), 0)
    assert store.stats.host_grows == 1
    assert store.capacity_bytes == 4 * store.row_bytes == store.nbytes
    # a clear frees two slots; two new ids take them
    store.apply(np.array([0, 1, 16, 16]), none, np.array([1, 1, 0, 0], bool),
                np.zeros(4, int), _block(rng, template, 4), 1)
    store.apply(np.array([4, 5, 16, 16]), np.array([1, 1, 0, 0], bool), none,
                np.ones(4, int), _block(rng, template, 4), 1)
    assert len(store) == 4 and store.stats.host_grows == 1
    assert store.stats.rows_cleared == 2
    # a prune frees the round-0 rows; new ids reuse those slots too
    store.prune(3)
    assert store.ids() == [4, 5]
    store.apply(np.array([6, 7, 16, 16]), np.array([1, 1, 0, 0], bool), none,
                np.full(4, 3), _block(rng, template, 4), 3)
    assert len(store) == 4 and store.stats.host_grows == 1
    # a fifth live row grows one more chunk
    store.apply(np.array([8, 16, 16, 16]), np.array([1, 0, 0, 0], bool),
                none, np.full(4, 3), _block(rng, template, 4), 3)
    assert store.stats.host_grows == 2
    assert store.capacity_bytes == 8 * store.row_bytes
    store.clear()
    assert len(store) == 0 and store.ids() == [] and store.nbytes == 0
    assert store.capacity_bytes == 8 * store.row_bytes
    blk = _block(rng, template, 4)
    for rnd in range(2):
        store.apply(np.arange(4) + 4 * rnd, ones, none, np.full(4, rnd),
                    blk, rnd)
    assert store.stats.host_grows == 2 and len(store) == 8
    assert not store.gather(np.array([16, 99, -1]))["w"].any()


@pytest.mark.parametrize("x", [1, 7, 64])
def test_store_reads_cohort_minor_blocks(x):
    """A block that stores its cohort axis innermost (the TPU's default
    layout for the trainer's cache block) is written exactly like the
    same values stored row by row."""
    rng = np.random.default_rng(x)
    template = {"w": np.zeros((9, 5), np.float32),
                "b": np.zeros((5,), np.float32),
                "v": np.zeros((3, 2, 4), np.float64)}
    n = 3 * x
    block = _block(rng, template, x)
    minor = {k: np.moveaxis(np.ascontiguousarray(np.moveaxis(v, 0, -1)),
                            -1, 0) for k, v in block.items()}
    assert not minor["w"].flags.c_contiguous or x == 1
    first, idx = rng.permutation(n)[:x], rng.permutation(n)[:x]
    write = rng.random(x) < 0.6
    stores = []
    for blk in (block, minor):
        store = CS.HostCacheStore(template, n)
        # a first round spreads the slots over two chunks
        store.apply(first, np.ones(x, bool),
                    np.zeros(x, bool), np.zeros(x, int), block, 0)
        store.apply(idx, write, np.zeros(x, bool), np.ones(x, int), blk, 1)
        stores.append(store)
    probe = np.arange(n + 1)
    for k in template:
        np.testing.assert_array_equal(stores[1].gather(probe)[k],
                                      stores[0].gather(probe)[k])
    got = stores[1].gather(idx[write])
    for k in template:
        np.testing.assert_array_equal(got[k], block[k][write])


def test_store_duplicate_ids_last_write_wins():
    """An id repeated in one apply keeps its last write or clear."""
    template = _template()
    store = CS.HostCacheStore(template, num_clients=8)
    blk = _block(np.random.default_rng(1), template, 4)
    store.apply(np.array([3, 3, 5, 5]), np.array([1, 1, 1, 0], bool),
                np.array([0, 0, 0, 1], bool), np.array([1, 2, 3, 4]), blk, 2)
    assert store.ids() == [3] and store.stamp_of(3) == 2
    np.testing.assert_array_equal(store.gather(np.array([3]))["w"][0],
                                  blk["w"][1])
    assert store.stats.rows_written == 1


def test_gather_returns_owned_arrays():
    """``gather`` hands out fresh arrays: neither later fetches (which
    reuse the stream's staging blocks) nor writes to the result touch
    the other."""
    rng = np.random.default_rng(2)
    template = _template()
    store = CS.HostCacheStore(template, num_clients=8)
    stream = CS.CohortCacheStream(store)
    idx = np.array([1, 2, 8])
    store.apply(idx, np.ones(3, bool), np.zeros(3, bool), np.zeros(3, int),
                _block(rng, template, 3), 0)
    got = store.gather(idx)
    again = store.gather(idx)
    keep = {k: v.copy() for k, v in got.items()}
    assert all(got[k] is not again[k] for k in got)
    for rnd in range(3):
        stream.fetch(np.array([2, 1, 5][: rnd + 1] + [8] * (2 - rnd)), rnd)
        staging = [leaf for blk in stream._blocks for leaf in blk]
        assert not any(np.shares_memory(v, leaf)
                       for v in got.values() for leaf in staging)
    for k in got:
        np.testing.assert_array_equal(got[k], keep[k])
    got["w"][:] = np.inf
    np.testing.assert_array_equal(store.gather(idx)["w"], keep["w"])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_staging_block_equals_gather_across_rounds(seed):
    """Every block the stream puts equals a fresh ``store.gather(idx)``
    bit for bit, across rounds whose hits move (rows that held hits in
    a staging block's previous use read as zeros again), and still does
    after the next fetch, which fills the other staging block."""
    rng = np.random.default_rng(seed)
    n, x = 10, 6
    template = _template()
    store = CS.HostCacheStore(template, n)
    stream = CS.CohortCacheStream(store)
    prev = None
    for rnd in range(10):
        idx = np.full(x, n, np.int64)
        k = int(rng.integers(0, x + 1))
        idx[:k] = np.sort(rng.choice(n, size=k, replace=False))
        put = stream.fetch(idx, rnd)
        want = store.gather(idx)
        for p, w in ((put, want),) if prev is None else ((put, want), prev):
            for name in template:
                np.testing.assert_array_equal(np.asarray(p[name]), w[name],
                                              err_msg=f"r{rnd} {name}")
        prev = (put, want)
        op = rng.integers(0, 3, size=x)
        stream.stage(idx, op == 0, op == 1, _block(rng, template, x),
                     np.full(x, rnd))
    assert store.stats.rows_hit > 0


def test_stream_counters_equal_mask_sums():
    """``rows_written`` is the sum of the write masks over known ids,
    ``rows_hit`` the ids of each fetched block that held a row, and
    ``rows_cleared`` the rows a clear released."""
    rng = np.random.default_rng(5)
    n, x = 20, 8
    template = _template()
    store = CS.HostCacheStore(template, n)
    stream = CS.CohortCacheStream(store)
    live = set()
    want = {"rows_written": 0, "rows_hit": 0, "rows_cleared": 0}
    for rnd in range(10):
        idx = np.full(x, n, np.int64)
        k = int(rng.integers(1, x + 1))
        idx[:k] = np.sort(rng.choice(n, size=k, replace=False))
        stream.fetch(idx, rnd)          # drains the previous write-back
        want["rows_hit"] += sum(int(c) in live for c in idx)
        op = rng.integers(0, 3, size=x)
        write, clear = op == 0, op == 1
        stream.stage(idx, write, clear, _block(rng, template, x),
                     np.full(x, rnd))
        want["rows_written"] += int((write & (idx < n)).sum())
        want["rows_cleared"] += sum(int(c) in live for c in idx[clear])
        live |= {int(c) for c in idx[write & (idx < n)]}
        live -= {int(c) for c in idx[clear]}
        stream.drain(rnd)
        assert store.ids() == sorted(live)
    got = store.stats.snapshot()
    assert {k: got[k] for k in want} == want
    assert got["host_grows"] * x * store.row_bytes == store.capacity_bytes


def test_store_matches_device_expiry_predicate():
    """Host prune and device ``expire_caches`` share one predicate
    (``current_round - stamp > bound``) — a row is pruned iff its device
    metadata was expired, so the planner can never resume a pruned row."""
    bound = 3
    stamps = np.array([-1, 0, 2, 5, 9], np.int32)
    rnd = 9
    caches = core.ClientCaches({}, np.full(5, 0.5, np.float32),
                               jnp.asarray(stamps))
    expired = np.asarray(
        core.expire_caches(caches, rnd, bound).round_stamp) < 0
    host_dead = np.array([rnd - int(s) > bound for s in stamps])
    # empty slots (stamp -1) read expired either way
    np.testing.assert_array_equal(expired, host_dead | (stamps < 0))


# ---------------------------------------------------------------------------
# Offload-vs-resident golden parity (single device, bit-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", sorted(available_policies()))
def test_policy_parity_offload_vs_resident(policy, data):
    """Every registered policy: the host-offload path replays the
    resident cohort History bit for bit."""
    bounded = policy not in ("mifa", "asyncfeded")
    fl = FL if bounded else dataclasses.replace(FL, cohort_size=N)
    resident = _run(data, fl, policy)
    offload = _run(data, dataclasses.replace(fl, cache_offload="host"),
                   policy)
    _assert_hist_equal(resident, offload, policy)


def test_parity_padded_cohort_and_depths(data):
    """Sentinel-padded cohorts and pipelined depths change nothing."""
    resident = _run(data, FL, "flude")
    for x in (12, N):
        for depth in (1, 4):
            fl = dataclasses.replace(FL, cohort_size=x,
                                     cache_offload="host",
                                     pipeline_depth=depth)
            _assert_hist_equal(resident, _run(data, fl, "flude"),
                               f"X={x} depth={depth}")


def test_parity_discard_with_uncrossed_bound(data):
    """A staleness bound the run never crosses makes ``"discard"``
    bit-identical to ``"host"`` (and so to the resident path)."""
    resident = _run(data, FL, "flude")
    fl = dataclasses.replace(FL, cache_offload="discard",
                             cache_staleness_bound=SIM.rounds + 10)
    _assert_hist_equal(resident, _run(data, fl, "flude"), "discard")


def test_parity_repeated_runs_reset_store(data):
    """Back-to-back runs on one engine reset the host store with the
    device caches — run 2 replays run 1 (and the resident engine)."""
    fl = dataclasses.replace(FL, cache_offload="host")
    engine = FleetEngine(data, SIM, fl)
    h1 = engine.run("flude", diagnostics=False)
    h2 = engine.run("flude", diagnostics=False)
    _assert_hist_equal(h1, h2, "rerun")
    _assert_hist_equal(_run(data, FL, "flude"), h2, "vs resident")


def test_parity_with_stateful_rule(data):
    """The offload server step threads the stateful robust-aggregation
    state exactly like the resident one (trust scores included)."""
    fl = dataclasses.replace(FL, agg_rule="trust")
    resident = _run(data, fl, "flude")
    offload = _run(data, dataclasses.replace(fl, cache_offload="host"),
                   "flude")
    _assert_hist_equal(resident, offload, "trust")
    np.testing.assert_array_equal(resident.trust, offload.trust)


def test_rerun_allocates_no_host_memory(data):
    """A second identical run on one engine reuses the first run's
    slabs: no chunk is allocated, the capacity is unchanged and the
    row counters repeat exactly."""
    fl = dataclasses.replace(FL, cache_offload="host")
    engine = FleetEngine(data, dataclasses.replace(SIM, rounds=6), fl)
    runs = []
    for _ in range(2):
        engine.transfer_stats.reset()
        engine.run("flude", diagnostics=False)
        runs.append((engine.transfer_stats.snapshot(),
                     engine.cache_store.capacity_bytes))
    (first, cap1), (second, cap2) = runs
    assert first["host_grows"] >= 1 and first["rows_written"] > 0
    assert second["host_grows"] == 0 and cap2 == cap1
    for key in ("rows_written", "rows_cleared", "rows_hit"):
        assert second[key] == first[key], key
    assert cap1 >= engine.cache_store.nbytes


def _record_stream(engine):
    """Record, per fetch, the rows the stream's gather hit and, per
    drain, the rows the store wrote."""
    stream, stats = engine._cache_stream, engine.transfer_stats
    rec = {"hits": [], "written": []}
    gather, apply = stream._gather, stream.store.apply

    def counted(fn, key, counter):
        def call(*a, **k):
            before = getattr(stats, counter)
            out = fn(*a, **k)
            rec[key].append(getattr(stats, counter) - before)
            return out
        return call

    stream._gather = counted(gather, "hits", "rows_hit")
    stream.store.apply = counted(apply, "written", "rows_written")
    return rec


# ---------------------------------------------------------------------------
# Streaming contract: async only, O(1) per round, O(X) bytes
# ---------------------------------------------------------------------------

def test_stream_never_blocks_a_round(data):
    """The protocol's invariant: zero synchronous copies; every blocking
    read is on a handle whose device-to-host copy was issued before it,
    and each copy is read once; one fetch + one write-back stage per
    round."""
    fl = dataclasses.replace(FL, cache_offload="host")
    engine = FleetEngine(data, SIM, fl)
    engine.run("flude", diagnostics=False)          # compile + place
    engine.transfer_stats.reset()
    rec = _record_stream(engine)
    engine.run("flude", rounds=3, diagnostics=False)
    s = engine.transfer_stats.snapshot()
    assert s["sync_copies"] == 0
    # per round: one d2h dispatch for the fetch's idx, one for the
    # staged (X,) arrays and one for the compacted rows of a round that
    # wrote any; one h2d for the hit rows and their positions
    compactions = sum(w > 0 for w in rec["written"])
    assert s["d2h_async"] == 2 * 3 + compactions
    assert s["h2d_async"] == 3
    assert s["pre_issued_reads"] == s["d2h_async"]


def test_stream_transfers_round_count_independent(data):
    """Per-round transfer work is constant: counts scale linearly in
    rounds with zero fixed-point drift, and the rows that cross are the
    live ones, bucket-padded: below the (X, ...) block whenever a round
    hits fewer than X rows, and never N·D."""
    x = N
    fl = dataclasses.replace(FL, cache_offload="host", cohort_size=x)
    engine = FleetEngine(data, SIM, fl)
    engine.run("flude", diagnostics=False)
    row = engine.cache_store.row_bytes
    buckets = CS.row_buckets(x)
    rec = _record_stream(engine)
    per_run = []
    for rounds in (1, 3):
        engine.transfer_stats.reset()
        rec["hits"].clear()
        rec["written"].clear()
        engine.run("flude", rounds=rounds, diagnostics=False)
        s = engine.transfer_stats.snapshot()
        compactions = sum(w > 0 for w in rec["written"])
        assert s["d2h_async"] - compactions == 2 * rounds
        assert s["h2d_async"] == rounds
        # exactly the padded live rows cross, plus the (bucket,) int32
        # positions up and the (X,) arrays down
        puts = [CS._bucket(buckets, h) for h in rec["hits"]]
        assert s["h2d_bytes"] == sum(puts) * (row + 4)
        assert all(p * (row + 4) < x * row
                   for p, h in zip(puts, rec["hits"]) if h < x)
        assert s["h2d_bytes"] < rounds * x * row
        small = s["d2h_bytes"] - s["d2h_rows"] * row
        per_run.append(small / rounds)
        assert 0 < small <= rounds * x * 16
    assert per_run[0] == per_run[1]


class _Opaque:
    """A device handle stand-in: a shape and a dtype, and no value."""

    def __init__(self, shape, dtype):
        self.shape, self.dtype = shape, np.dtype(dtype)

    def __array__(self, *args, **kwargs):
        raise AssertionError("stage() read a leaf")


def test_stage_reads_no_leaf():
    """``stage`` only parks handles and starts copies: it counts bytes
    from shapes and reads no leaf, so it never waits on the device."""
    x = 6
    template = _template()
    store = CS.HostCacheStore(template, num_clients=10)
    stream = CS.CohortCacheStream(store)
    block = {k: _Opaque((x,) + v.shape, v.dtype)
             for k, v in template.items()}
    stream.stage(_Opaque((x,), np.int32), _Opaque((x,), bool),
                 _Opaque((x,), bool), block, _Opaque((x,), np.int32))
    s = store.stats.snapshot()
    assert s["d2h_async"] == 1 and s["pre_issued_reads"] == 0
    assert s["d2h_bytes"] == x * (4 + 1 + 1 + 4)
    assert s["d2h_rows"] == 0 and s["sync_copies"] == 0
    stream.reset()
    assert stream._pending is None


def _edge_rounds(x, step):
    """(ids, write) per round, crossing the bucket edges: no hit and
    every row written, then every row a hit and exactly one bucket
    written, then one hit and one row written over new ids."""
    first = np.arange(x)
    one = np.zeros(x, bool)
    one[:step] = True
    later = np.arange(x, 2 * x)
    single = np.zeros(x, bool)
    single[0] = True
    return [(first, np.ones(x, bool)), (first, one),
            (np.concatenate([first[:1], later[1:]]), single),
            (later, np.zeros(x, bool))]


@pytest.mark.parametrize("x", [8, 24, 40])
def test_stream_bit_identical_across_bucket_edges(x):
    """Rounds with no hit, with every row written and with exactly one
    bucket of writes leave the store bit-identical to a store fed the
    whole (X, ...) blocks, every fetched block equals ``gather``, and
    ``h2d_rows``/``d2h_rows`` are the bucket-rounded hits and writes."""
    rng = np.random.default_rng(x)
    n = 2 * x
    template = _template()
    store = CS.HostCacheStore(template, n)
    ref = CS.HostCacheStore(template, n)
    stream = CS.CohortCacheStream(store)
    buckets = CS.row_buckets(x)
    want_rows = {"h2d_rows": 0, "d2h_rows": 0}
    for rnd, (idx, write) in enumerate(_edge_rounds(x, buckets[0])):
        hits = sum(ref.stamp_of(c) is not None for c in idx)
        put = stream.fetch(idx, rnd)
        want = ref.gather(idx)
        for k in template:
            np.testing.assert_array_equal(np.asarray(put[k]), want[k])
        clear = ~write & (rng.random(x) < 0.3)
        blk = _block(rng, template, x)
        stream.stage(idx, write, clear, blk, np.full(x, rnd))
        ref.apply(idx, write, clear, np.full(x, rnd), blk, rnd)
        want_rows["h2d_rows"] += CS._bucket(buckets, hits)
        want_rows["d2h_rows"] += CS._bucket(buckets, int(write.sum()))
        stream.drain(rnd)
        assert store.ids() == ref.ids()
        probe = np.arange(n + 1)
        for k in template:
            np.testing.assert_array_equal(store.gather(probe)[k],
                                          ref.gather(probe)[k])
        assert [store.stamp_of(c) for c in probe] == \
            [ref.stamp_of(c) for c in probe]
    got = store.stats.snapshot()
    assert {k: got[k] for k in want_rows} == want_rows
    assert got["rows_hit"] > 0


def test_row_buckets_from_cohort_size():
    """Multiples of max(8, ceil(X/16)) rows up to X — at most 16 sizes
    — and, on a mesh, multiples of its device count."""
    assert CS.row_buckets(8) == [8]
    assert CS.row_buckets(12) == [8, 12]
    assert CS.row_buckets(16) == [8, 16]
    assert CS.row_buckets(512) == list(range(32, 513, 32))
    assert len(CS.row_buckets(1000)) == 16
    assert CS.row_buckets(1000)[-1] == 1000
    assert CS.row_buckets(48, devices=16) == [16, 32, 48]
    for x in (1, 7, 100, 4096):
        assert 1 <= len(CS.row_buckets(x)) <= 16
    assert CS._bucket([8, 16], 0) == 0
    assert CS._bucket([8, 16], 9) == 16


class _Compiles:
    """Counts JAX traces and backend compiles while open."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def test_second_run_compiles_nothing(data):
    """Every bucket's expansion and compaction is compiled in the first
    run: a second run on one engine, whose hits and writes differ,
    compiles and traces nothing."""
    fl = dataclasses.replace(FL, cache_offload="host", cohort_size=N)
    engine = FleetEngine(data, dataclasses.replace(SIM, rounds=2), fl)
    engine.run("flude", diagnostics=False)
    stream = engine._cache_stream
    assert sorted(stream._expand) == [0] + CS.row_buckets(N)
    assert sorted(stream._compact) == CS.row_buckets(N)
    with _Compiles() as compiles:
        engine.run("flude", rounds=5, diagnostics=False)
    assert compiles.count == 0


def test_stream_rows_are_bucket_rounded_live_rows(data):
    """Over an engine run, ``h2d_rows`` is each fetch's hits and
    ``d2h_rows`` each drain's writes, rounded up to a bucket, and the
    History, a first round with no hit included, is the resident
    path's bit for bit."""
    sim = dataclasses.replace(SIM, rounds=6)
    resident = dataclasses.replace(FL, cohort_size=24)
    fl = dataclasses.replace(resident, cache_offload="host")
    engine = FleetEngine(data, sim, fl)
    rec = _record_stream(engine)
    hist = engine.run("flude", diagnostics=False)
    _assert_hist_equal(FleetEngine(data, sim, resident).run(
        "flude", diagnostics=False), hist)
    assert rec["hits"][0] == 0
    s = engine.transfer_stats.snapshot()
    buckets = CS.row_buckets(24)
    assert s["rows_hit"] > 0 and s["rows_written"] > 0
    assert s["h2d_rows"] == sum(CS._bucket(buckets, h)
                                for h in rec["hits"])
    assert s["d2h_rows"] == sum(CS._bucket(buckets, w)
                                for w in rec["written"])
    assert s["rows_hit"] <= s["h2d_rows"] < 6 * 24
    assert s["rows_written"] <= s["d2h_rows"]


def test_no_stream_transfers_without_cache(data):
    """``uses_cache=False`` policies skip the stream entirely — the
    offload engine feeds the trainer a constant zeros block."""
    fl = dataclasses.replace(FL, cache_offload="host")
    engine = FleetEngine(data, SIM, fl)
    engine.run("random", diagnostics=False)
    assert engine.transfer_stats.snapshot() == CS.TransferStats().snapshot()
    assert len(engine.cache_store) == 0


def test_offload_adds_no_per_round_uploads(data, monkeypatch):
    """The ``place_per_client`` seam: offload rounds upload exactly what
    resident cohort rounds upload — the cache stream's own transfers go
    through ``device_put``/``copy_to_host_async``, never through the
    per-client placement path."""
    import repro.fl.engine as ENG
    import repro.fl.policies as POL
    import repro.fl.simulator as SIMM

    counts = {"n": 0}
    orig = SIMM.place_per_client

    def counting(arr, mesh=None):
        counts["n"] += 1
        return orig(arr, mesh)

    for mod in (ENG, POL, SIMM):
        monkeypatch.setattr(mod, "place_per_client", counting)

    per_path = {}
    for label, fl in (("resident", FL),
                      ("offload",
                       dataclasses.replace(FL, cache_offload="host"))):
        engine = FleetEngine(data, SIM, fl)
        engine.run("flude", diagnostics=False)      # compile + place
        per_run = []
        for rounds in (1, 3):
            counts["n"] = 0
            engine.run("flude", rounds=rounds, diagnostics=False)
            per_run.append(counts["n"])
        assert per_run[0] == per_run[1], (label, per_run)
        per_path[label] = per_run[0]
    assert per_path["offload"] == per_path["resident"], per_path


# ---------------------------------------------------------------------------
# Memory profile: device O(X·D), host = live rows, rule state
# ---------------------------------------------------------------------------

def test_server_step_memory_reports_residency_split(data):
    x = FL.cohort_size
    resident = FleetEngine(data, SIM, FL)
    offload = FleetEngine(data, SIM,
                          dataclasses.replace(FL, cache_offload="host"))
    mr = resident.server_step_memory()
    mo = offload.server_step_memory()
    row = offload.cache_store.row_bytes
    meta = N * (4 + 4)                    # (N,) f32 progress + i32 stamp
    assert mr["cache_host_bytes"] == 0
    assert mr["cache_device_bytes"] == meta + N * row
    # offload device residency is O(X·D) + O(N) metadata — fleet-size
    # independent in the model dimension
    assert mo["cache_device_bytes"] == meta + x * row
    assert mo["cache_device_bytes"] < mr["cache_device_bytes"]
    assert mo["cache_host_bytes"] == 0    # nothing stored before a run
    engine = FleetEngine(data, SIM,
                         dataclasses.replace(FL, cache_offload="host"))
    engine.run("flude", diagnostics=False)
    after = engine.server_step_memory()
    assert after["cache_host_bytes"] == \
        len(engine.cache_store) * row


def test_server_step_memory_reports_rule_state(data):
    mr = FleetEngine(data, SIM, FL).server_step_memory()
    assert mr["rule_state_bytes"] == 0
    mt = FleetEngine(
        data, SIM, dataclasses.replace(FL, agg_rule="trust")
    ).server_step_memory()
    assert mt["rule_state_bytes"] == N * 4     # (N,) float32 trust


# ---------------------------------------------------------------------------
# Discard staleness semantics on the live store
# ---------------------------------------------------------------------------

def test_discard_prunes_stale_store_rows(data):
    sim = dataclasses.replace(SIM, rounds=8)
    fl = dataclasses.replace(FL, cache_offload="discard",
                             cache_staleness_bound=1)
    engine = FleetEngine(data, sim, fl)
    engine.run("flude", diagnostics=False)
    # every surviving row was written within the bound of the final
    # prune (run end drains at round ``rounds``)
    for cid in engine.cache_store.ids():
        assert sim.rounds - engine.cache_store.stamp_of(cid) <= 1
    loose = FleetEngine(data, sim,
                        dataclasses.replace(fl, cache_staleness_bound=64))
    loose.run("flude", diagnostics=False)
    assert len(engine.cache_store) <= len(loose.cache_store)


# ---------------------------------------------------------------------------
# Sharded (8 forced host devices) offload round path
# ---------------------------------------------------------------------------

def _run_script(script, timeout=540):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


_MESH_SCRIPT = r"""
from repro.launch.mesh import force_host_platform_device_count
force_host_platform_device_count(8)
import dataclasses
import json
import jax

from repro.configs.base import FLConfig
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, SimConfig

n = 32
data = federated_classification(n, seed=0, n_per_client=32)
sim = SimConfig(num_clients=n, rounds=3, seed=0, local_steps=2)
out = {"n_dev": len(jax.devices()), "cases": {}}

for pol, x in (("flude", 8), ("mifa", 32)):
    fl = FLConfig(num_clients=n, clients_per_round=8, dynamics="markov",
                  mesh_shape=(8,), cohort_size=x)
    ref = FleetEngine(data, sim, fl).run(pol, diagnostics=False)
    engine = FleetEngine(data, sim,
                         dataclasses.replace(fl, cache_offload="host"))
    h = engine.run(pol, diagnostics=False)
    out["cases"][f"{pol}-x{x}"] = {
        "hist_equal": (h.acc == ref.acc and h.comm_mb == ref.comm_mb
                       and h.wall_clock == ref.wall_clock
                       and h.received == ref.received
                       and h.selected == ref.selected),
        "sync_copies": engine.transfer_stats.sync_copies,
        "store_rows": len(engine.cache_store),
    }
print(json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_offload_round_path():
    """Offload vs resident cohort over 8 forced host devices: the two
    paths dispatch the same cohort ops over the same rows (the fetched
    block lands on the cohort sharding), so the full History — floats
    included — is bit-identical, with zero synchronous copies."""
    rec = _run_script(_MESH_SCRIPT)
    assert rec["n_dev"] == 8
    for case, r in rec["cases"].items():
        assert r["hist_equal"], (case, r)
        assert r["sync_copies"] == 0, (case, r)
