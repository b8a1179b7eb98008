"""Model flops of the served row: each position through every layer and
each sampled position through the LM head.

Per layer and position: the GAR linears at the row's ranks,
``2 * (d_out + d_in - r) * r`` each (the identity block is neither stored
nor multiplied), and attention over ``p + 1`` keys,
``4 * heads * head_dim * (p + 1)``. Per sampled position: the LM head,
``2 * d_model * vocab``. Norms, RoPE and softmax are left out.
"""
from __future__ import annotations


def linear_flops(groups, ranks) -> float:
    """Per position, summed over layers: ``groups`` are the reference's
    (path, layers, d_out, d_in), ``ranks`` the row's rank per path."""
    return sum(2.0 * layers * (d_out + d_in - ranks[path]) * ranks[path]
               for path, layers, d_out, d_in in groups)


def work(its, model: dict, per_position: float) -> float:
    h = model["num_heads"]
    hd = model["d_model"] // h
    layers = model["num_layers"]
    flops = 0.0
    for it in its:
        keys = it.n * it.start + it.n * (it.n + 1) // 2
        flops += per_position * it.n + 4.0 * h * hd * keys * layers
        if it.sampled:
            flops += 2.0 * model["d_model"] * model["vocab_size"]
    return flops
