"""How late the load generator submitted the window's requests behind
their due times, p95: a starved generator would otherwise read as a fast
server (its lateness is part of every TTFT, which counts from due)."""
LAYER, UNIT, MOVES = "client", "ms", "ttft_p90_ms"


def read(ctx):
    late = [max(0.0, r.sent - r.due) * 1e3 for r in ctx.window_records
            if r.sent is not None]
    return ctx.pct(late, 0.95) if late else None
