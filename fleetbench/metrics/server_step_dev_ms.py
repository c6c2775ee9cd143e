"""Device time per round of the fused server step (weights, packed
aggregation, cache bookkeeping)."""
from fleetbench import tracing

MODULE = r"^jit_server_round_step"


def read(ctx):
    ns = tracing.total_by(ctx.trace["modules"], MODULE)
    return ns * 1e-6 / ctx.rounds if ns > 0 else None
