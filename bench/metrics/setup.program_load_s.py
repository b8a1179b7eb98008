"""Seconds of XLA compiles and persistent-cache loads from the engine's
construction until the window opens, summed over every compiling thread,
from the program's process-wide compile record (``repro.obs.compiles``).
Notes name the programs that compiled or loaded inside the window, which
should be none."""
LAYER, UNIT, MOVES = "compiled programs", "s", "setup_s"


def read(ctx):
    try:
        from repro.obs.compiles import RECORD
    except ImportError:
        return None
    if RECORD.engine_t is None:
        return None
    events = list(RECORD.events)
    setup = [c for c in events if RECORD.engine_t <= c.t_end < ctx.run.open]
    ctx.notes["setup.programs"] = {
        "count": len(setup),
        "cache_hits": sum(1 for c in setup if c.cache == "hit"),
        "in_window": [c.fun_name for c in events
                      if ctx.run.open <= c.t_end < ctx.run.close]}
    return sum(c.seconds for c in setup) if setup else None
