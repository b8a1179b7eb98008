"""Blocks of the paged KV pool in use over the pool, mean over the
window's iterations (the engine's occupancy counter): how much of the
memory reserved for the cache the traffic holds."""
LAYER, UNIT, MOVES = "paged KV cache", "%", "tokens_per_s"


def read(ctx):
    occ = [o for t, _, _, o in ctx.run.metrics.steps
           if ctx.run.open <= t < ctx.run.close]
    return 100.0 * sum(occ) / len(occ) if occ else None
