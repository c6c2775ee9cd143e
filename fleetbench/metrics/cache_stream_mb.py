"""Megabytes the host cache stream moves per round, both ways (the
program's own TransferStats over the traced window)."""


def read(ctx):
    if ctx.spec["fl"].get("cache_offload") is None:
        return None
    return ctx.counters["transfer_bytes"] / 1e6 / ctx.rounds
