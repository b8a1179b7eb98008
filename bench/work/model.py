"""Model flops of the served row: each position through every group and
every layer that attends, and each sampled position through the LM head.

Per position: each GAR group at the row's rank, ``2 * (d_out + d_in - r)
* r`` (the identity block is neither stored nor multiplied) times the
instances of the group a position passes through (``per_position``: its
layers, times ``top_k`` for an expert stack); attention in each layer
that attends, over ``p + 1`` keys or its window, ``2 * heads * (qk_dim +
v_dim)`` a key. Per sampled position: the LM head, ``2 * d_model *
vocab``. Norms, RoPE, softmax and routers are left out.
"""
from __future__ import annotations

from collections import Counter

import items


def linear_flops(groups, ranks) -> float:
    """Per position: ``groups`` are the architecture module's (path,
    lead_shape, d_out, d_in, per_position), ``ranks`` the row's rank per
    path."""
    return sum(2.0 * (d_out + d_in - ranks[path]) * ranks[path] * per_position
               for path, _, d_out, d_in, per_position in groups)


def work(its, shape: dict, per_position: float) -> float:
    per_key = 2.0 * shape["heads"] * (shape["qk_dim"] + shape["v_dim"])
    layers = Counter(shape["windows"])
    flops = 0.0
    for it in its:
        attention = sum(per_key * items.keys(it, window) * n
                        for window, n in layers.items())
        flops += per_position * it.n + attention
        if it.sampled:
            flops += 2.0 * shape["d_model"] * shape["vocab_size"]
    return flops
