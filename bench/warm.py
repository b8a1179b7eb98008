"""Warm the program shapes a cell can reach, before the window.

The engine compiles one fused step program per (token width, block-table
width, sample rows, top-k or not) and one feed-fixup program per
(token width, fixups, previous sample rows). Its bucketing keeps these
sets finite; this module enumerates them by applying the engine's own
bucketing (``_bucket_tokens``, ``_bucket_rows``; the KV cache's table
widths, powers of two capped at its ``padded_max_blocks``) to every token
count, sample count and sequence length the settings admit.

Every fixup shape is warmed (each compiles in well under a second). Step
programs take seconds each to compile and to load from the cache, so only
those a loaded server meets are warmed: every token width and row bucket
at tables at least half the widest, and the ramp's chunk-wide iterations
(at least ``prefill_chunk`` tokens, few sampled rows, top-k on or off)
at every table. What is left needs a server all but empty, every live
sequence short or every sampled row greedy; a run prints the compiles its
window saw.

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


def table_widths(cache):
    """The KV cache's table-width rule (``active_max_blocks``) over every
    sequence length: smallest power of two >= its blocks, capped."""
    out = set()
    for blocks in range(1, cache.max_blocks_per_seq + 1):
        t = 1
        while t < blocks:
            t *= 2
        out.add(min(t, cache.padded_max_blocks))
    return sorted(out)


def shapes(engine, cache):
    """(step shapes, fixup shapes) to warm.

    Step: (token width, table width, sample rows, top-k). Rows come from at
    most ``min(tokens, max_batch)`` sampled positions (decode slots and
    finishing chunks, one slot each), zero included; top-k is off whenever
    every sampled row is greedy. Fixup: (token width, fixups, previous
    rows), a fixup patching one continuing decode slot from the previous
    iteration's sampled rows."""
    mb = engine.max_batch
    pairs, rows_all = set(), set()
    for used in range(1, engine._mixed_budget + 1):
        w = engine._bucket_tokens(used)
        for n in range(0, min(used, mb) + 1):
            r = engine._bucket_rows(n)
            pairs.add((w, r, min(used, mb)))
            rows_all.add(r)
    wide = cache.padded_max_blocks // 2
    ramp_width = engine._bucket_tokens(engine.prefill_chunk)
    rows_low = sorted(rows_all)[:2]

    def kept(w, t, r, topk):
        ramp = w >= ramp_width and r <= rows_low[-1]
        if not topk:
            return ramp and r == rows_low[0]
        return t >= wide or ramp

    step = sorted({(w, t, r, topk) for w, r, _ in pairs
                   for t in table_widths(cache) for topk in (True, False)
                   if kept(w, t, r, topk)})
    most = {}
    for w, _, n in pairs:
        most[w] = max(most.get(w, 0), n)
    fixup = sorted({(w, f, r) for w, n in most.items() for r in rows_all
                    for f in range(1, min(n, r) + 1)})
    return step, fixup


def _sampling(rows: int, topk: bool):
    z = jnp.zeros(rows, jnp.int32)
    return {"temperature": jnp.asarray(np.full(rows, 0.8, np.float32)),
            "top_k": jnp.asarray(np.full(rows, 40, np.int32)) if topk else None,
            "seed": z, "req_id": z, "purpose": z, "position": z}


def _step_args(engine, params, pools, w, t, r, topk):
    mb = engine.max_batch
    caches = {
        "slot_ids": jnp.asarray(np.full(w, mb, np.int32)),   # the null slot
        "positions": jnp.asarray(np.zeros(w, np.int32)),
        "block_tables": jnp.asarray(np.zeros((mb + 1, t), np.int32)),
        "segments": pools,
        "sample_ids": jnp.asarray(np.zeros(r, np.int32)),
    }
    return (params, caches, np.zeros((1, w), np.int32), _sampling(r, topk))


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
