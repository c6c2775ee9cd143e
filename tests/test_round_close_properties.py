"""Hypothesis property tests: the jitted device round cut
(``core.make_round_cut``) matches the numpy reference
(``core.host_round_cut``) bit-for-bit on float32 times.

The deterministic seeded sweep in tests/test_round_close.py covers the
same invariant without the hypothesis dependency; this module widens the
search space (randomized fleet sizes, inf-heavy times, fractional and
edge quorums, both straggler traits) where hypothesis is available.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

settings.register_profile("round_close", max_examples=60, deadline=None)
settings.load_profile("round_close")

DEADLINE = 600.0


def _times(n, inf_rate, seed, deadline=DEADLINE):
    rng = np.random.RandomState(seed)
    t = rng.uniform(1.0, 2.0 * deadline, n).astype(np.float32)
    t[rng.rand(n) < inf_rate] = np.inf
    return t


def _check(times, quorum, waits, deadline=DEADLINE):
    """Jitted cut == numpy reference under the ledger's billing rule
    (``deadline if capped else float(t_cut)``)."""
    times = np.asarray(times, np.float32)
    success = np.isfinite(times)
    t_ref, d_ref = core.host_round_cut(times, quorum, deadline, waits)
    cut = core.make_round_cut(times.shape[0], deadline, waits)
    t_dev, recv, capped = cut(jnp.asarray(times), quorum,
                              jnp.asarray(success))
    billed = deadline if bool(capped) else float(t_dev)
    assert billed == t_ref, (billed, t_ref)
    assert billed == d_ref
    # receive reference: float32 compare against the float32-nearest cast
    # of the host cut (the engine's receive semantics since PR 4)
    np.testing.assert_array_equal(
        np.asarray(recv), success & (times <= np.float32(t_ref)))


@given(st.integers(1, 64), st.floats(0.0, 1.0), st.data(),
       st.integers(0, 2 ** 31 - 1), st.booleans())
def test_cut_matches_host_reference(n, inf_rate, data, seed, waits):
    times = _times(n, inf_rate, seed)
    quorum = data.draw(st.one_of(
        st.integers(0, n).map(float),
        st.floats(0.0, float(n), allow_nan=False).map(
            lambda q: float(np.float32(q)))))
    _check(times, quorum, waits)


@given(st.integers(1, 48), st.integers(0, 2 ** 31 - 1), st.booleans())
def test_cut_quorum_edges_0_1_N(n, seed, waits):
    """The quorum corner cases: 0 (idle round), 1, exactly N, and one
    more than the finite count (unmet quorum)."""
    for inf_rate in (0.0, 0.5, 1.0):
        times = _times(n, inf_rate, seed)
        finite = int(np.isfinite(times).sum())
        for q in (0.0, 1.0, float(n), float(min(finite + 1, n))):
            _check(times, q, waits)


@pytest.mark.parametrize("waits", [True, False])
def test_cut_subnormal_quorum_counts_one_upload(waits):
    """A positive quorum below float32's normal range still waits for
    one upload: XLA flushes subnormals to zero on the device, where the
    host's ``ceil`` gives 1."""
    quorum = float(np.float32(1e-40))
    assert 0.0 < quorum < float(np.finfo(np.float32).tiny)
    for inf_rate in (0.0, 0.5, 1.0):
        _check(_times(16, inf_rate, 3), quorum, waits)


@given(st.integers(1, 48), st.floats(0.3, 1.0),
       st.integers(0, 2 ** 31 - 1))
def test_cut_async_last_arrival(n, inf_rate, seed):
    """Async designs close at the last arrival when the quorum is not
    met (and never receive anything past the deadline)."""
    times = _times(n, inf_rate, seed)
    finite = np.sort(times[np.isfinite(times)])
    _check(times, float(finite.size + 1), waits=False)


@given(st.integers(1, 32), st.integers(0, 2 ** 31 - 1), st.booleans(),
       st.sampled_from([5.0, 50.0, 600.0, 100.3, 600.1, 3599.9997]))
def test_cut_deadline_cap(n, seed, waits, deadline):
    """Deadline caps bill the exact float64 config value even when it is
    not float32-representable (100.3, 600.1, ...)."""
    times = _times(n, 0.3, seed, deadline=deadline)
    for q in (1.0, float(n)):
        _check(times, q, waits, deadline=deadline)
