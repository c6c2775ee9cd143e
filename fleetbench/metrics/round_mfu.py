"""Training and evaluation FLOPs of the window over its length and the
chip's bf16 peak: local steps actually run by the selected clients (from
the reference's fleet simulation, which the check holds equal to the
run) times batch, forward and backward, plus one forward pass of the
test set per evaluated round, each counted by the cell's model file."""
from fleetbench.peaks import peaks


def read(ctx):
    spec = ctx.spec
    model, sim, code = spec["model"], spec["sim"], spec["model_code"]
    samples = ctx.counters["completed_steps"] * int(sim["batch_size"])
    evals = ctx.rounds // int(spec["eval_every"])
    flops = code.train_flops(model, samples) + code.eval_flops(
        model, evals * int(spec["data"]["n_test"]))
    peak = peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / ctx.window_s / peak
