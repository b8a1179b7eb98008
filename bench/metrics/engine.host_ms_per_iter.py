"""Host milliseconds per engine iteration inside the window: the
``ServingMetrics`` dispatch/host split's host part (planning, cache
bookkeeping, commits), which the lookahead pipeline runs under the
device's work."""
LAYER, UNIT, MOVES = "engine", "ms", "itl_p50_ms"


def read(ctx):
    host = [h for t, _, h in ctx.run.metrics.timings
            if ctx.run.open <= t < ctx.run.close]
    return 1e3 * sum(host) / len(host) if host else None
