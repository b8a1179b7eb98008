"""Warm the program shapes a cell can reach, before the window.

The engine compiles one fused step program per (token width, sample rows)
and one feed-fixup program per (token width, fixups, previous sample
rows). Every step passes block tables ``padded_max_blocks`` wide and a
``top_k`` array (zeros where a row does not truncate), so neither the table
width nor top-k multiplies the step programs. Its bucketing keeps these
sets finite; this module enumerates them by applying the engine's own
bucketing (``_bucket_tokens``, ``_bucket_rows``) to every token count and
sample count the settings admit, and warms every one of them: nothing
that a run can dispatch is left to compile in its window, and a run
prints the compiles its window saw.

Each program is lowered on this thread and compiled on a few others (XLA
compiles with the interpreter lock released; a bounded number in flight
keeps host memory in hand), then run once on null operands (pad tokens
routed to the null block row, so no live state is touched). With JAX's
persistent cache only the first run in a checkout compiles; later runs
trace and load.
"""
from __future__ import annotations

import collections
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np


def shapes(engine, cache):
    """(step shapes, fixup shapes) to warm.

    Step: (token width, table width, sample rows). Rows come from at most
    ``min(tokens, max_batch)`` sampled positions (decode slots and
    finishing chunks, one slot each), zero included; the table is always
    ``padded_max_blocks`` wide. Fixup: (token width, fixups, previous
    rows), a fixup patching one continuing decode slot from the previous
    iteration's sampled rows."""
    mb = engine.max_batch
    pairs = set()
    for used in range(1, engine._mixed_budget + 1):
        w = engine._bucket_tokens(used)
        for n in range(0, min(used, mb) + 1):
            pairs.add((w, engine._bucket_rows(n), min(used, mb)))
    step = sorted({(w, cache.padded_max_blocks, r) for w, r, _ in pairs})
    rows_all = {r for _, r, _ in pairs}
    most = {}
    for w, _, n in pairs:
        most[w] = max(most.get(w, 0), n)
    fixup = sorted({(w, f, r) for w, n in most.items() for r in rows_all
                    for f in range(1, min(n, r) + 1)})
    return step, fixup


def _sampling(rows: int):
    z = jnp.zeros(rows, jnp.int32)
    return {"temperature": jnp.asarray(np.full(rows, 0.8, np.float32)),
            "top_k": jnp.asarray(np.full(rows, 40, np.int32)),
            "seed": z, "req_id": z, "purpose": z, "position": z}


def _step_args(engine, params, pools, w, t, r):
    mb = engine.max_batch
    caches = {
        "slot_ids": jnp.asarray(np.full(w, mb, np.int32)),   # the null slot
        "positions": jnp.asarray(np.zeros(w, np.int32)),
        "block_tables": jnp.asarray(np.zeros((mb + 1, t), np.int32)),
        "segments": pools,
        "sample_ids": jnp.asarray(np.zeros(r, np.int32)),
    }
    return (params, caches, np.zeros((1, w), np.int32), _sampling(r))


def _fixup_args(w, f, r):
    return (np.zeros((1, w), np.int32), np.arange(f, dtype=np.int32),
            jnp.zeros(r, jnp.int32), np.zeros(f, np.int32))


def warm(engine, params, cache, threads: int = 3):
    """Compile the step and fixup shapes, then run each once. ``cache`` is
    a ``PagedKVCache`` of the engine's settings; its pools are donated and
    dropped. Returns the number of programs."""
    step_fn, fixup_fn = engine._sample_jit, engine._fixup_jit
    step, fixup = shapes(engine, cache)
    pools = cache.pools
    lowerings = ([lambda s=s: step_fn.lower(*_step_args(
        engine, params, pools, *s)) for s in step]
        + [lambda s=s: fixup_fn.lower(*_fixup_args(*s)) for s in fixup])
    with ThreadPoolExecutor(threads) as pool:
        flight = collections.deque()
        for lower in lowerings:
            if len(flight) >= threads:
                flight.popleft().result()
            flight.append(pool.submit(lower().compile))
        for job in flight:
            job.result()
    tokens = None
    for s in step:
        tokens, new = step_fn(*_step_args(engine, params, pools, *s))
        pools = [dict(p) for p in new["segments"]]
    out = None
    for s in fixup:
        out = fixup_fn(*_fixup_args(*s))
    for x in (tokens, out):
        if x is not None:
            x.block_until_ready()
    return len(step) + len(fixup)
