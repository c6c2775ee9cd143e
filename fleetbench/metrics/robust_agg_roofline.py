"""Roofline share of the geometric median's kernels: every Pallas kernel
the robust server step runs (weighted sums and residual norms), against
the least work the smoothed Weiszfeld iteration needs: one read of the
(X, D) buffer for the initial mean and one per step."""
from fleetbench import counts, tracing
from fleetbench.peaks import peaks

KERNELS = r"^%(fed_agg_pallas|residual_norms_pallas)"


def read(ctx):
    fl = ctx.spec["fl"]
    if fl.get("agg_rule") != "geometric_median" or fl["agg_impl"] != "pallas":
        return None
    ns = tracing.total_by(ctx.trace["ops"], KERNELS)
    if ns <= 0:
        return None
    iters = int(dict(fl.get("agg_rule_params", {})).get("iters", 6))
    rows = int(fl["cohort_size"])
    dim = ctx.spec["model_code"].packed_dim(ctx.spec["model"])
    least = ctx.rounds * (iters + 1) * counts.agg_bytes(rows, dim) \
        / peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * least / (ns * 1e-9)
