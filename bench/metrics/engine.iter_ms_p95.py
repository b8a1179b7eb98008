"""p95 over the window's engine iterations of the time between consecutive
commit ends, from the program's own per-iteration record
(``ServingMetrics.iterations``; an iteration is in the window when its
commit ends inside it): the gaps the engine itself opens between tokens,
before the hand-off to the clients. Notes split the gaps by whether the
iteration carried prompt chunks, and count the window iterations that
compiled, rolled back or collected garbage."""
LAYER, UNIT, MOVES = "engine", "ms", "itl_p50_ms"


def read(ctx):
    recs = [r for r in getattr(ctx.run.metrics, "iterations", ())
            if r.t_commit is not None]
    gaps = {True: [], False: []}
    stalls = {"compiles": 0, "rollbacks": 0, "gc": 0}
    for a, b in zip(recs, recs[1:]):
        if ctx.run.open <= b.t_commit < ctx.run.close:
            gaps[b.prefill_tokens > 0].append((b.t_commit - a.t_commit) * 1e3)
            stalls["compiles"] += b.compiles > 0
            stalls["rollbacks"] += b.rollback is not None
            stalls["gc"] += b.gc_s > 0
    every = gaps[True] + gaps[False]
    if not every:
        return None
    ctx.notes["engine.iter_ms"] = dict(
        {f"{kind}_p50_p95_n": [round(ctx.pct(g, 0.5), 3),
                               round(ctx.pct(g, 0.95), 3), len(g)]
         for kind, g in (("prefill", gaps[True]), ("decode", gaps[False]))
         if g}, **stalls)
    return ctx.pct(every, 0.95)
