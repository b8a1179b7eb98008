"""Share of the traced window in which no operation ran on the device:
one minus the union of the device op intervals over the window, in
percent (``busy_s`` and ``window_s`` on the result line give it as a
fraction)."""
LAYER, UNIT, MOVES = "device", "%", "tokens_per_s"


def read(ctx):
    if ctx.red is None:
        return None
    return 100.0 * (1.0 - ctx.red["busy_s"] / ctx.red["window_s"])
