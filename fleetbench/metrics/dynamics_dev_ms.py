"""Device time per round of the availability process's jitted step."""
from fleetbench import tracing

MODULE = r"^jit_step\("


def read(ctx):
    ns = tracing.total_by(ctx.trace["modules"], MODULE)
    return ns * 1e-6 / ctx.rounds if ns > 0 else None
