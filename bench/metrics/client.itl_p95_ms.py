"""p95 of the gaps between consecutive tokens of the window's requests, as
the clients stamped them (``e2e.gaps_ms``): the tail of the inter-token
latency, the gaps that an iteration carrying prompt chunks opens. At the
chat cell's load its runs spread too widely for an end-to-end bound, so it
is read here, beside ``engine.iter_ms_p95``, the same tail as the engine
records it before the hand-off to the clients."""
LAYER, UNIT, MOVES = "client", "ms", "itl_p50_ms"


def read(ctx):
    import e2e
    gaps = e2e.gaps_ms(ctx.run)
    return ctx.pct(gaps, 0.95) if gaps else None
