"""Useful work of the paged attention kernel (``paged_prefill_attention``,
which serves every token of a mixed iteration, decode tokens included).

Per layer, a token at position ``p`` attends over ``p + 1`` keys: QK^T and
PV cost ``2 * 2 * heads * head_dim * (p + 1)`` flops. Bytes are what the
work cannot do without: its queries and outputs once, and the keys and
values of each run's context once (a chunk's tokens share one sequence),
all float32.
"""
from __future__ import annotations


def work(its, model: dict):
    h, kv = model["num_heads"], model["num_kv_heads"]
    hd = model["d_model"] // h
    layers = model["num_layers"]
    flops = nbytes = 0.0
    for it in its:
        keys = it.n * it.start + it.n * (it.n + 1) // 2     # sum of p + 1
        flops += 4.0 * h * hd * keys
        nbytes += 4.0 * (2 * it.n * h * hd + 2 * (it.start + it.n) * kv * hd)
    return flops * layers, nbytes * layers
