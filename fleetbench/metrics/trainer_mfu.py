"""The local trainer's share of the chip's bf16 peak while it runs: the
model file's training FLOPs of the local steps the selected clients
completed (from the reference's fleet simulation, which the check holds
equal to the run) times batch, over the device time of the
``^jit_train_`` modules in the traced window."""
from fleetbench import tracing
from fleetbench.peaks import peaks

MODULE = r"^jit_train_"


def read(ctx):
    ns = tracing.total_by(ctx.trace["modules"], MODULE)
    if ns <= 0:
        return None
    spec = ctx.spec
    samples = ctx.counters["completed_steps"] * int(spec["sim"]["batch_size"])
    flops = spec["model_code"].train_flops(spec["model"], samples)
    peak = peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / (ns * 1e-9) / peak
