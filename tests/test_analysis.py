"""Static-analysis package tests: HLO contract checks, the invariant
auditor, the repo lint, and the ``debug_checks`` runtime sanitizers.

Negative paths first — every checker must *fire* on an injected
violation, naming the dispatch — then the clean paths: a real engine
audits clean, and the repo itself lints clean (the same gates the
``analysis-smoke`` CI job runs).

The checkers are pure functions over HLO text / python source, so most
cases run on synthetic inputs; the auditor smoke and the runtime-guard
tests drive a real single-device engine.
"""
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo_checks as HC
from repro.analysis import lint as L
from repro.analysis import runtime as RT
from repro.analysis.audit import (audit_engine, check_transfer_stats,
                                  transfer_ceiling)
from repro.configs.base import FLConfig
from repro.core.cache_store import TransferStats
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, SimConfig
from repro.analysis.hlo_checks import count_aliases
from repro.roofline.hlo import _parse_shape, analyze_hlo_text

_REPO = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------------------
# Contract checkers on synthetic HLO
# ---------------------------------------------------------------------------

def test_alias_block_counts_nested_entries():
    text = ('HloModule jit_step, input_output_alias={ {0}: (0, {}, '
            'may-alias), {1}: (2, {}, must-alias), {2}: (3, {}, '
            'may-alias) }, entry_computation_layout={(f32[4])->f32[4]}')
    assert count_aliases(text) == 3
    assert count_aliases("HloModule jit_f, num_partitions=2") == 0


def test_check_donation_fires_and_names_dispatch():
    text = "HloModule jit_step, input_output_alias={ {0}: (0, {}, may-alias) }"
    bad = HC.check_donation("server_step", text, min_aliases=3)
    assert len(bad) == 1
    assert bad[0].dispatch == "server_step"
    assert bad[0].contract == "donation"
    assert "found 1" in bad[0].message
    assert HC.check_donation("server_step", text, min_aliases=1) == []


def test_donation_on_real_jit():
    """A real donated jit aliases; the undonated twin does not."""
    x = jnp.zeros((8, 4))
    donated = jax.jit(lambda v: v + 1, donate_argnums=0).lower(x).compile()
    plain = jax.jit(lambda v: v + 1).lower(x).compile()
    assert HC.check_donation("d", donated.as_text(), 1) == []
    dropped = HC.check_donation("d", plain.as_text(), 1)
    assert len(dropped) == 1 and dropped[0].contract == "donation"


def test_check_no_host_ops_flags_injected_callback():
    """A jax.debug.callback compiled into a dispatch is exactly the
    python round-trip the zero-sync contract bans."""
    def leaky(v):
        jax.debug.callback(lambda a: None, v)
        return v * 2

    text = jax.jit(leaky).lower(jnp.ones(4)).compile().as_text()
    bad = HC.check_no_host_ops("trainer", text)
    assert bad, "injected host callback not flagged"
    assert bad[0].dispatch == "trainer"
    assert bad[0].contract == "host-sync"
    assert "callback" in bad[0].message


def test_check_no_host_ops_clean_on_plain_jit():
    text = jax.jit(lambda v: v @ v.T).lower(jnp.ones((4, 4))) \
        .compile().as_text()
    assert HC.check_no_host_ops("trainer", text) == []


def test_check_no_host_ops_flags_infeed_and_host_memory_space():
    text = """HloModule m

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %tok = token[] after-all()
  %in = (f32[4]{0}, token[]) infeed(%tok)
  %h = f32[4]{0:S(5)} copy(%p0)
  ROOT %r = f32[4]{0} add(%p0, %p0)
}
"""
    bad = HC.check_no_host_ops("round_cut", text)
    contracts = {f.contract for f in bad}
    assert contracts == {"host-sync"}
    msgs = " | ".join(f.message for f in bad)
    assert "infeed" in msgs and "host-memory-space" in msgs


def test_check_no_f64_flags_upcast():
    with jax.enable_x64(True):
        text = jax.jit(lambda v: v * 2).lower(
            jnp.ones(4, jnp.float64)).compile().as_text()
    bad = HC.check_no_f64("metrics", text)
    assert len(bad) == 1
    assert bad[0].dispatch == "metrics" and bad[0].contract == "dtype"
    clean = jax.jit(lambda v: v * 2).lower(jnp.ones(4)).compile().as_text()
    assert HC.check_no_f64("metrics", clean) == []


def test_check_psum_dtype():
    text = """HloModule m

%sum (a: bf16[], b: bf16[]) -> bf16[] {
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %s = bf16[] add(%a, %b)
}

ENTRY %main (p0: bf16[8]) -> bf16[8] {
  %p0 = bf16[8]{0} parameter(0)
  ROOT %ar = bf16[8]{0} all-reduce(%p0), to_apply=%sum
}
"""
    bad = HC.check_psum_dtype("server_step", text)
    assert len(bad) == 1 and "bf16" in bad[0].message
    # f32 float psum and integer (ledger-count) psum are both fine
    ok = text.replace("bf16", "f32")
    assert HC.check_psum_dtype("server_step", ok) == []
    ints = text.replace("bf16", "s32")
    assert HC.check_psum_dtype("server_step", ints) == []


def test_check_partition_count():
    text = "HloModule jit_f, num_partitions=8"
    assert HC.check_partition_count("trainer", text, 8) == []
    bad = HC.check_partition_count("trainer", text, 4)
    assert len(bad) == 1 and bad[0].contract == "sharding"
    # absent annotation reads as 1 (the silent single-device fallback)
    lone = HC.check_partition_count("trainer", "HloModule jit_f", 8)
    assert "num_partitions=1" in lone[0].message


class _FakeSharding:
    def __init__(self, replicated):
        self.is_fully_replicated = replicated


def test_check_input_shardings_flags_replicated_fleet_operand():
    n, x = 32, 8
    leaves = [np.zeros((n,)), np.zeros((x, 4)), np.zeros((3,))]
    shardings = [_FakeSharding(True), _FakeSharding(False),
                 _FakeSharding(True)]
    bad = HC.check_input_shardings("flude_plan", leaves, shardings, (n, x))
    assert len(bad) == 1
    assert bad[0].dispatch == "flude_plan"
    assert "operand #0" in bad[0].message
    # small non-fleet arrays may replicate freely
    ok = HC.check_input_shardings(
        "flude_plan", leaves,
        [_FakeSharding(False), _FakeSharding(False), _FakeSharding(True)],
        (n, x))
    assert ok == []


# ---------------------------------------------------------------------------
# Transfer ceiling (contract 5)
# ---------------------------------------------------------------------------

def _fake_engine(offload, **stats):
    ts = TransferStats()
    for k, v in stats.items():
        setattr(ts, k, v)
    return types.SimpleNamespace(offload=offload, transfer_stats=ts)


def test_transfer_ceiling_is_zero_without_offload_or_cache():
    zeros = {"d2h_async": 0, "h2d_async": 0,
             "pre_issued_reads": 0, "sync_copies": 0}
    assert transfer_ceiling(_fake_engine(None), True) == zeros
    assert transfer_ceiling(_fake_engine(object()), False) == zeros
    assert transfer_ceiling(_fake_engine(object()), True) == {
        "d2h_async": 3, "h2d_async": 1,
        "pre_issued_reads": 3, "sync_copies": 0}


def test_check_transfer_stats_flags_sync_copy_and_excess():
    eng = _fake_engine(object(), d2h_async=9, h2d_async=3,
                       pre_issued_reads=9, sync_copies=0)
    assert check_transfer_stats(eng, rounds=3, uses_cache=True) == []
    eng = _fake_engine(object(), d2h_async=10, sync_copies=1)
    bad = check_transfer_stats(eng, rounds=3, uses_cache=True)
    keys = {f.message.split("=")[0] for f in bad}
    assert keys == {"d2h_async", "sync_copies"}
    assert all(f.contract == "transfer" for f in bad)


# ---------------------------------------------------------------------------
# Auditor smoke on a real engine (single device)
# ---------------------------------------------------------------------------

def _small_engine(**fl_kw):
    n = 16
    data = federated_classification(n, num_classes=3, dim=8,
                                    n_per_client=12, n_test=24, seed=1)
    sim = SimConfig(num_clients=n, rounds=3, local_steps=2, batch_size=6,
                    model_hidden=8, model_depth=1, seed=0)
    fl = FLConfig(num_clients=n, clients_per_round=8, dynamics="markov",
                  **fl_kw)
    return FleetEngine(data, sim, fl)


def test_audit_engine_clean_on_real_round_path():
    engine = _small_engine(donate_buffers=True)
    report = audit_engine(engine, "flude")
    assert report.ok(), report.summary()
    assert report.mode == "full" and report.mesh_size == 1
    for name in ("trainer", "round_cut", "server_step", "flude_plan",
                 "eval_accuracy"):
        assert name in report.dispatches, report.dispatches
    assert "all contracts hold" in report.summary()


def test_audit_report_raise_names_every_violation():
    engine = _small_engine()
    report = audit_engine(engine, "flude")
    report.findings.append(HC.Finding("trainer", "dtype", "injected"))
    with pytest.raises(AssertionError, match=r"\[dtype\] trainer"):
        report.raise_on_findings()


# ---------------------------------------------------------------------------
# debug_checks runtime sanitizers
# ---------------------------------------------------------------------------

def test_round_guard_fires_on_nonfinite_model():
    guard = RT.make_round_guard(8, with_idx=False)
    err, _ = guard({"w": jnp.array([1.0, jnp.nan])}, jnp.zeros(4))
    with pytest.raises(RT.RoundCheckError, match="round 5"):
        RT.throw_round_error(err, 5)
    err, _ = guard({"w": jnp.ones(2)}, jnp.zeros(4))
    RT.throw_round_error(err, 5)     # clean: no raise


def test_round_guard_checks_cohort_index_bounds():
    guard = RT.make_round_guard(8, with_idx=True)
    # N == 8 is the legal pad sentinel; 9 is out of bounds
    err, _ = guard({"w": jnp.ones(2)}, jnp.zeros(4),
                   jnp.array([0, 8], jnp.int32))
    RT.throw_round_error(err, 0)
    err, _ = guard({"w": jnp.ones(2)}, jnp.zeros(4),
                   jnp.array([0, 9], jnp.int32))
    with pytest.raises(RT.RoundCheckError, match="out of bounds"):
        RT.throw_round_error(err, 0)


def test_recompilation_detector_raises_on_retrace():
    sizes = {"n": 1}

    class _Jit:
        def _cache_size(self):
            return sizes["n"]

    eng = types.SimpleNamespace(
        _server_steps={"k": _Jit()}, _dyn_cache={}, _cut_fns={},
        _metrics_fns={}, _trainer=None, _acc_fn=None, _idx_fn=None,
        _expire_fn=None, _cache_reset=None)
    det = RT.RecompilationDetector(eng)
    det.check()                      # baseline
    det.check()                      # stable: fine
    sizes["n"] = 2
    with pytest.raises(RT.RoundCheckError, match="re-traced"):
        det.check()


def test_debug_checks_engine_run_is_observation_only():
    plain = _small_engine().run("flude", diagnostics=False)
    checked = _small_engine(debug_checks=True).run(
        "flude", diagnostics=False)
    assert checked.acc == plain.acc
    assert checked.received == plain.received


# ---------------------------------------------------------------------------
# Repo lint: fixture self-tests + clean repo
# ---------------------------------------------------------------------------

def _rules(findings):
    return sorted({f.rule for f in findings})


def test_lint_flags_host_syncs_in_round_path_modules():
    src = ("import jax\n"
           "import numpy as np\n"
           "def hot(x):\n"
           "    a = jax.device_get(x)\n"
           "    b = np.asarray(x)\n"
           "    c = x.item()\n"
           "    d = float(run(x))\n"
           "    return a, b, c, d\n")
    bad = L.lint_source(src, "repro/fl/engine.py")
    assert len(bad) == 4 and _rules(bad) == ["host-sync"]
    # same code outside a round-path module is not the lint's business
    assert L.lint_source(src, "repro/obs/report.py") == []
    # allowlisted seams are exempt, nested defs included
    seam = src.replace("def hot", "def host_round_cut")
    assert L.lint_source(seam, "repro/core/round.py") == []


def test_lint_flags_mutable_global_but_not_frozen_configs():
    bad = L.lint_source("STATS = TransferStats()\n",
                        "repro/core/cache_store.py")
    assert "mutable-global" in _rules(bad)
    ok = L.lint_source("CONFIG = ModelConfig(dim=4)\n",
                       "repro/configs/transformer.py")
    assert ok == []
    # lowercase module attrs and non-constructor calls are not flagged
    assert L.lint_source("helper = Maker()\nX = compute()\n",
                         "repro/fl/api.py") == []


def test_lint_flags_undocumented_or_computed_registry_names():
    src = ("@register_policy(NAME)\n"
           "def my_policy(cfg):\n"
           "    return 1\n")
    bad = L.lint_source(src, "repro/fl/policies.py")
    assert _rules(bad) == ["registry"] and len(bad) == 2   # name + docstring
    ok = ("@register_policy(\"mine\")\n"
          "def my_policy(cfg):\n"
          "    \"\"\"Documented.\"\"\"\n"
          "    return 1\n")
    assert L.lint_source(ok, "repro/fl/policies.py") == []


def test_lint_flags_nondeterminism_inside_jit():
    src = ("import jax, time\n"
           "@jax.jit\n"
           "def step(x):\n"
           "    return x * time.time()\n")
    bad = L.lint_source(src, "repro/core/round.py")
    assert "jit-determinism" in _rules(bad)
    ok = ("import jax, time\n"
          "def host_side():\n"
          "    return time.time()\n")
    assert L.lint_source(ok, "repro/obs/trace.py") == []


def test_lint_flags_deprecated_stats_references():
    bad = L.lint_source("from repro.core.cache_store import STATS\n",
                        "repro/fl/engine.py")
    assert "deprecated-stats" in _rules(bad)
    bad = L.lint_source("import repro.core.cache_store as CS\n"
                        "def f():\n"
                        "    CS.STATS.reset()\n",
                        "repro/obs/report.py")
    assert "deprecated-stats" in _rules(bad)


def test_lint_requires_post_init_registry_validation():
    src = ("class FLConfig:\n"
           "    def __post_init__(self):\n"
           "        pass\n")
    bad = L.lint_source(src, "repro/configs/base.py")
    assert len(bad) == len(L._POST_INIT_VALIDATORS)
    assert _rules(bad) == ["registry"]


def test_repo_lints_clean():
    """The gate the analysis-smoke CI job enforces on every push."""
    findings = L.lint_paths([os.path.join(_REPO, "src", "repro")])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_config_rejects_unknown_dynamics():
    with pytest.raises(ValueError, match="dynamics"):
        FLConfig(num_clients=8, dynamics="not-a-registered-name")


# ---------------------------------------------------------------------------
# roofline/hlo.py regressions (satellite: parse gaps)
# ---------------------------------------------------------------------------

def test_parse_shape_tuple_and_fp8_dtypes():
    el, by = _parse_shape("(f32[128,4]{1,0}, f32[128,4], u32[])")
    assert el == 128 * 4 * 2 + 1
    assert by == 128 * 4 * 4 * 2 + 4
    el, by = _parse_shape("f8e4m3fnuz[32]")
    assert (el, by) == (32, 32)
    el, by = _parse_shape("(f8e5m2fnuz[8], u2[16], s2[4])")
    assert (el, by) == (8 + 16 + 4, 8 + 16 + 4)


def test_copy_start_done_pair_charged_once():
    """The async pair moves the buffer once: 2x buffer bytes at the
    start (read + write), nothing at the completion handle.  The old
    fall-through summed the tuple output and the pair ~6x."""
    text = """HloModule m

ENTRY %main (p0: f32[128]) -> f32[128] {
  %p0 = f32[128]{0} parameter(0)
  %cs = (f32[128]{0}, f32[128]{0}, u32[]) copy-start(%p0)
  ROOT %cd = f32[128]{0} copy-done(%cs)
}
"""
    cost = analyze_hlo_text(text)
    assert cost.bytes == 2 * 128 * 4
