"""Roofline share of the fed_agg Pallas kernel in a mean-rule cell: the
least time one weighted sum over the (X, D) cohort buffer needs (the
larger of its bytes over HBM bandwidth and its FLOPs over peak), over
the kernel's measured device time.  D is the unpadded count of trainable
parameters, from the cell's model file."""
from fleetbench import counts, tracing
from fleetbench.peaks import peaks

KERNEL = r"^%fed_agg_pallas"


def read(ctx):
    fl = ctx.spec["fl"]
    if fl.get("agg_rule", "mean") != "mean" or fl["agg_impl"] != "pallas":
        return None
    ns = tracing.total_by(ctx.trace["ops"], KERNEL)
    if ns <= 0:
        return None
    p = peaks(ctx.device_kind)
    rows = int(fl["cohort_size"])
    dim = ctx.spec["model_code"].packed_dim(ctx.spec["model"])
    least = ctx.rounds * max(counts.agg_bytes(rows, dim)
                             / p["hbm_bytes_per_s"],
                             counts.agg_flops(rows, dim)
                             / p["bf16_flops_per_s"])
    return 100.0 * least / (ns * 1e-9)
