"""Milliseconds per window iteration that the engine thread spent blocked
handing tokens and finished results to the clients' streams
(``StreamSession.emit``/``finish``, the record's ``emit_s``): the
cross-thread hand-off inside each commit."""
LAYER, UNIT, MOVES = "stream", "ms", "itl_p50_ms"


def read(ctx):
    emit = [r.emit_s for r in getattr(ctx.run.metrics, "iterations", ())
            if r.t_commit is not None
            and ctx.run.open <= r.t_commit < ctx.run.close]
    return 1e3 * sum(emit) / len(emit) if emit else None
