"""Useful work of the paged attention kernel (``paged_prefill_attention``,
which serves every token of a mixed iteration, decode tokens included).

Per layer that attends, a token at position ``p`` attends over ``p + 1``
keys, or its window: QK^T and PV cost ``2 * heads * (qk_dim + v_dim)``
flops a key. Bytes are what the work cannot do without: its queries (at
the qk width) and outputs (at the v width) once, and each run's context
once per kv head at ``kv_width``, the lanes a token's keys and values take
in the pool (a chunk's tokens share one sequence), all float32.
"""
from __future__ import annotations

from collections import Counter

import items


def work(its, shape: dict):
    h, kv = shape["heads"], shape["kv_heads"]
    width = shape["qk_dim"] + shape["v_dim"]
    flops = nbytes = 0.0
    for window, layers in Counter(shape["windows"]).items():
        f = b = 0.0
        for it in its:
            f += 2.0 * h * width * items.keys(it, window)
            b += 4.0 * (it.n * h * width
                        + items.context(it, window) * kv * shape["kv_width"])
        flops += f * layers
        nbytes += b * layers
    return flops, nbytes
