"""Device time per round of FLUDE's fused belief update and plan
(``jit_update_plan``), over the rounds that run it: every round but the
first, whose plan has no update to fold in."""
from fleetbench import tracing

MODULE = r"^jit_update_plan\("


def read(ctx):
    if ctx.spec["policy"] != "flude" or ctx.rounds < 2:
        return None
    ns = tracing.total_by(ctx.trace["modules"], MODULE)
    return ns * 1e-6 / (ctx.rounds - 1) if ns > 0 else None
