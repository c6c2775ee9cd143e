"""The check fails a run whose timed path is broken underneath, and
fails the control (the reference in bfloat16) and the half-batch fault
against the benchmarked cell's limits."""
import jax.numpy as jnp
import pytest

from fleetbench import control
from fleetbench.tests.tiny import FILE_CELLS, file_cell, run, tiny


def _unchanged_state(monkeypatch):
    from repro.core import aggregation as AGG
    from repro.core import agg_rules as AR
    monkeypatch.setattr(AGG, "fed_aggregate_packed",
                        lambda g, c, w, layout=None, **kw: g)
    monkeypatch.setattr(AR.GeometricMedianRule, "reduce",
                        lambda self, buf, gvec, w, **kw: gvec)


def _half_batch(monkeypatch):
    from repro.core import aggregation as AGG
    orig = AGG.aggregation_weights

    def half(received, **kw):
        r = jnp.asarray(received).astype(jnp.int32)
        keep = jnp.cumsum(r) <= (r.sum() + 1) // 2
        return jnp.where(keep, orig(received, **kw), 0.0)

    monkeypatch.setattr(AGG, "aggregation_weights", half)


def _answer_altered(monkeypatch):
    from repro import core
    orig = core.make_round_cut

    def make(*a, **kw):
        cut = orig(*a, **kw)

        def altered(*args):
            out = cut(*args)
            return out[:-3] + (out[-3] + 1,) + out[-2:]

        return altered

    monkeypatch.setattr(core, "make_round_cut", make)


FAULTS = {"unchanged_state": _unchanged_state, "half_batch": _half_batch,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("files", FILE_CELLS, ids=".".join)
def test_broken_timed_path_is_not_correct(files, fault, monkeypatch,
                                          tmp_path):
    FAULTS[fault](monkeypatch)
    res = run(tiny(file_cell(*files)), tmp_path, seconds=0.1)
    assert res["correct"] is False, res["checked"]


@pytest.mark.parametrize("files", FILE_CELLS, ids=".".join)
def test_control_and_half_batch_fail_the_limits(files):
    rows = control.readings(tiny(file_cell(*files)), seed=9)
    assert {r["variant"] for r in rows} == {"control", "half_batch"}
    assert all(r["fails"] for r in rows), rows
