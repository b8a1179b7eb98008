"""Device milliseconds per engine iteration spent in XLA ``copy`` ops
(``copy``, ``copy-start``, ``copy-done``) in the traced window: the KV
pools and operands moved rather than computed on."""
LAYER, UNIT, MOVES = "model step", "ms", "itl_p50_ms"


def read(ctx):
    if ctx.red is None or not ctx.trace_iterations:
        return None
    return 1e3 * ctx.op_seconds("copy") / ctx.trace_iterations
