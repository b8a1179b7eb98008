"""A test-only block: the repo's dense attention with the MoE FFN as the
program's ``moe_apply`` computes it.

The router in float32, its softmax over the experts, the ``top_k``
largest renormalised to sum to one; each routed expert a SwiGLU FFN of
width ``d_ff_expert``, their outputs weighted and summed; ``num_shared``
shared experts as one SwiGLU FFN of width ``num_shared * d_ff_shared``,
added. Segments of kind ``attn_dense`` keep the dense FFN of width
``d_ff``. No token is dropped: the configuration's ``capacity_factor``
has to give every expert room for every token of a step.
"""
from __future__ import annotations

import copy

import jax
import jax.numpy as jnp

from arch import common, dense


def _model(conf: dict) -> dict:
    return dense.model(conf, serves=("moe",))


def _shared_width(moe: dict) -> int:
    return moe["num_shared"] * (moe.get("d_ff_shared") or moe["d_ff_expert"])


def groups(conf: dict):
    m = _model(conf)
    d, moe = m["d_model"], m["moe"]
    out = []
    for i, (kind, count) in enumerate(dense.segments(m)):
        p = f"segments/{i}"
        out += dense.attn_groups(m, p, count)
        if kind == "attn_dense":
            out += dense.ffn_groups(f"{p}/mlp", count, d, m["d_ff"])
            continue
        # each position passes through top_k of the stack's experts a layer
        lead, f = (count, moe["num_experts"]), moe["d_ff_expert"]
        per = count * moe["top_k"]
        out += [(f"{p}/mlp/experts/down", lead, d, f, per),
                (f"{p}/mlp/experts/gate", lead, f, d, per),
                (f"{p}/mlp/experts/up", lead, f, d, per)]
        if moe["num_shared"]:
            out += dense.ffn_groups(f"{p}/mlp/shared", count, d,
                                    _shared_width(moe))
    return out


def leaves(conf: dict):
    m = _model(conf)
    out = common.embed_leaves(conf)
    for i, (kind, count) in enumerate(dense.segments(m)):
        p = f"segments/{i}"
        out += dense.block_norm_leaves(m, p, count)
        if kind == "attn":
            out.append((f"{p}/mlp/router/w",
                        (count, m["d_model"], m["moe"]["num_experts"]),
                        "normal", common.embed_std(conf)))
    return out + common.factor_leaves(conf, groups(conf))


def _moe(y, w, ranks, p: str, l: int, moe: dict, num):
    logits = jnp.matmul(y.astype(jnp.float32),
                        w[f"{p}/router/w"][l].astype(jnp.float32),
                        precision=num.precision)
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, moe["top_k"])
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)

    def expert(e):
        def lin(z, name):
            path = f"{p}/experts/{name}"
            return num.lin(z, w[f"{path}/u"][l, e], w[f"{path}/v"][l, e],
                           ranks[path])
        return lin(jax.nn.silu(lin(y, "gate")) * lin(y, "up"), "down")

    every = jnp.stack([expert(e) for e in range(moe["num_experts"])])
    picked = every[top_e, jnp.arange(y.shape[0])[:, None]]      # (S, K, d)
    out = jnp.sum(picked * num.cast(top_p)[..., None], axis=1)
    if moe["num_shared"]:
        out = out + dense.ffn(y, w, ranks, f"{p}/shared", l, num)
    return out


def forward(w: dict, ranks: dict, tokens, conf: dict, dtype=jnp.float32,
            precision=common.HIGHEST):
    m = _model(conf)
    num = common.Numerics(dtype, precision)
    x = num.cast(w["embed"])[tokens]
    for i, (kind, count) in enumerate(dense.segments(m)):
        p = f"segments/{i}"
        for l in range(count):
            x = dense.attention_layer(x, w, ranks, p, l, m, num)
            y = common.rms(x, w[f"{p}/ln_mlp"][l], m["norm_eps"])
            if kind == "attn_dense":
                x = x + dense.ffn(y, w, ranks, f"{p}/mlp", l, num)
            else:
                x = x + _moe(y, w, ranks, f"{p}/mlp", l, m["moe"], num)
    return common.head(x, w, conf, num)


def attention(conf: dict) -> dict:
    return dense.attention(conf, serves=("moe",))


def smoke(conf: dict) -> dict:
    """The fixture is at smoke size already."""
    return copy.deepcopy(conf)
