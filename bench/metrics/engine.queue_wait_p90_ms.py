"""Queue part of TTFT, p90 over the window's requests: the engine's own
``ServingMetrics`` time from its intake of a request to the request's
admission into a batch slot."""
LAYER, UNIT, MOVES = "engine", "ms", "ttft_p90_ms"


def read(ctx):
    waits = []
    for r in ctx.window_records:
        tr = ctx.run.metrics.traces.get(r.req_id)
        parts = tr.ttft_parts if tr is not None else None
        if parts is not None:
            waits.append(parts[0] * 1e3)
    return ctx.pct(waits, 0.90) if waits else None
