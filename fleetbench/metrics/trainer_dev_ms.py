"""Device time per round of the fused local trainer (round body)."""
from fleetbench import tracing

MODULE = r"^jit_train_"


def read(ctx):
    ns = tracing.total_by(ctx.trace["modules"], MODULE)
    return ns * 1e-6 / ctx.rounds if ns > 0 else None
