"""The FLOP and byte counts the per-layer metrics divide by: the MLP's
in ``models/mlp.py``, the aggregation's in ``counts.py``."""
import pytest

from fleetbench import counts, harness, tracing
from fleetbench.tests.tiny import file_cell

MLP = harness.load_model("mlp")
MODEL = {"kind": "mlp", "dim": 32, "hidden": 128, "depth": 2,
         "num_classes": 10}


def test_mlp_macs_by_hand():
    # 32x128 + 128x128 + 128x10
    assert MLP.mlp_macs(MODEL) == 4096 + 16384 + 1280 == 21760


def test_packed_dim_by_hand():
    assert MLP.packed_dim(MODEL) == (32 * 128 + 128) + (128 * 128 + 128) \
        + (128 * 10 + 10) == 22026


def test_train_and_eval_flops():
    assert MLP.train_flops(MODEL, 1) == 6 * 21760
    assert MLP.eval_flops(MODEL, 2048) == 2 * 21760 * 2048


def test_aggregation_bytes_and_flops():
    assert counts.agg_bytes(512, 199210) == 512 * 199210 * 4
    assert counts.agg_flops(512, 22026) == 2 * 512 * 22026


def ctx(spec, ops=(), modules=(), rounds=10, window_s=1.0, **counters):
    trace = {"ops": list(ops), "modules": list(modules), "devices": 1,
             "busy_s": 0.25, "window_s": window_s}
    base = {"transfer_bytes": 0, "completed_steps": 0, "selected": 0}
    base.update(counters)
    return tracing.Context(trace, rounds, window_s, spec, "TPU v5 lite",
                           base)


def spec(workload):
    config, traffic = workload.split(".")
    return harness.spec_of(file_cell(config, traffic))


def test_fed_agg_roofline_is_100_at_the_least_time():
    s = spec("xdevice-flude.diurnal")
    least = counts.agg_bytes(512, 199210) / 819e9       # memory bound
    ops = [("%fed_agg_pallas.1 = f32[1,200704] custom-call(...)", 0.0,
            least * 1e9)]
    read = harness.load_reader("fed_agg_roofline")
    assert read(ctx(s, ops, rounds=1)) == pytest.approx(100.0)
    assert read(ctx(s, ops, rounds=1, window_s=2.0)) == pytest.approx(100.0)
    ops2 = [(n, 0.0, 2 * d) for n, _, d in ops]
    assert read(ctx(s, ops2, rounds=1)) == pytest.approx(50.0)


def test_fed_agg_roofline_silent_without_its_kernel_or_rule():
    read = harness.load_reader("fed_agg_roofline")
    assert read(ctx(spec("xdevice-flude.diurnal"))) is None
    ops = [("%fed_agg_pallas.1 = custom-call", 0.0, 1e5)]
    assert read(ctx(spec("xdevice-flude-gm.signflip20"), ops)) is None


def test_robust_roofline_counts_seven_reads():
    s = spec("xdevice-flude-gm.signflip20")
    least = 7 * counts.agg_bytes(512, 199210) / 819e9
    ops = [("%fed_agg_pallas.3 = custom-call", 0.0, 0.5 * least * 1e9),
           ("%residual_norms_pallas.2 = custom-call", 0.0, 0.5 * least * 1e9),
           ("%fusion.1 = f32[8] fusion", 0.0, 1e9)]
    read = harness.load_reader("robust_agg_roofline")
    assert read(ctx(s, ops, rounds=1)) == pytest.approx(100.0)


def test_round_mfu_by_hand():
    s = spec("xdevice-flude.diurnal")
    c = ctx(s, rounds=2, window_s=0.5, completed_steps=4096)
    # the 2NN: 784*200 + 200*200 + 200*10 = 198,800 MACs a sample,
    # batch 10, 2,048 test samples a round
    flops = 6 * 198800 * 4096 * 10 + 2 * 198800 * 2048 * 2
    read = harness.load_reader("round_mfu")
    assert read(c) == pytest.approx(100 * flops / 0.5 / 197e12)


def test_module_readers_per_round():
    s = spec("xdevice-flude.diurnal")
    mods = [("jit_step(123)", 0.0, 2e6), ("jit_update_plan(9)", 0.0, 4e6),
            ("jit__lambda(7)", 0.0, 1e6), ("jit_train_cohort_dyn_offload(1)",
                                           0.0, 8e6),
            ("jit_server_round_step_cohort_offload(5)", 0.0, 6e6)]
    c = ctx(s, modules=mods, rounds=2)
    assert harness.load_reader("dynamics_dev_ms")(c) == pytest.approx(1.0)
    # the update+plan runs in every round but the first; the round-0
    # plan and other lambdas are not counted
    assert harness.load_reader("plan_dev_ms")(c) == pytest.approx(4.0)
    assert harness.load_reader("plan_dev_ms")(
        ctx(s, modules=mods, rounds=5)) == pytest.approx(1.0)
    assert harness.load_reader("trainer_dev_ms")(c) == pytest.approx(4.0)
    assert harness.load_reader("server_step_dev_ms")(c) == pytest.approx(3.0)
    mifa = spec("selectall-mifa.bernoulli")
    assert harness.load_reader("plan_dev_ms")(ctx(mifa, modules=mods)) \
        is None


def test_cache_stream_mb():
    read = harness.load_reader("cache_stream_mb")
    c = ctx(spec("xdevice-flude.diurnal"), rounds=4, transfer_bytes=4e8)
    assert read(c) == pytest.approx(100.0)
    assert read(ctx(spec("selectall-mifa.bernoulli"))) is None


def test_idle_share():
    read = harness.load_reader("idle_share")
    assert read(ctx(spec("xdevice-flude.diurnal"))) == pytest.approx(75.0)
