"""The repo's dense block, as the configuration states it.

Per layer: RMSNorm ``x * rsqrt(mean(x**2) + eps) * (1 + scale)``; q/k/v
projections, q/k RMS head norms, RoPE over the whole head, causal softmax
attention scaled by ``1/sqrt(D)``, grouped by ``num_kv_heads``, and the
output projection; RMSNorm and a SwiGLU FFN ``down(silu(gate x) * up x)``;
no biases. A tied or separate LM head. Segments of ``attn`` (or
``attn_dense``) layers, stated as a list of ``{kind, count}`` or as
``layers_per_segment``.

Other modules build on this one: ``attention_layer`` and ``ffn`` are the
block's two halves, ``segments`` and ``block_norm_leaves`` its layout.
"""
from __future__ import annotations

import copy
import math

import jax
import jax.numpy as jnp

from arch import common

KINDS = ("attn", "attn_dense")
# configuration keys this block does not compute
UNSERVED = ("moe", "mla", "ssm", "rwkv", "local_window", "attn_logit_softcap",
            "frontend_dim", "encoder_layers")


def segment_list(m: dict):
    """Every segment as a dict of ``Segment``'s fields, from ``segments``
    or from ``layers_per_segment``: the one reading of that form, which
    ``state.model_config`` takes too."""
    if "layers_per_segment" in m:
        per = m["layers_per_segment"]
        return [{"kind": "attn", "count": per}] * (m["num_layers"] // per)
    return m["segments"]


def segments(m: dict):
    """(kind, count) of every segment."""
    return [(s["kind"], s["count"]) for s in segment_list(m)]


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["num_heads"]


def model(conf: dict, serves=()) -> dict:
    """``conf["model"]``, refused where it states what the block does not
    compute; ``serves`` names the keys of ``UNSERVED`` that a block built
    on this one computes itself."""
    m = conf["model"]
    stated = [k for k in UNSERVED if m.get(k) and k not in serves]
    kinds = {kind for kind, _ in segments(m)} - set(KINDS)
    if stated or kinds:
        raise ValueError(f"{conf['name']}: the {conf['arch']} block does "
                         f"not compute {stated + sorted(kinds)}")
    return m


def attn_groups(m: dict, p: str, count: int):
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], head_dim(m)
    return [(f"{p}/attn/k", (count,), kv * hd, d, count),
            (f"{p}/attn/o", (count,), d, h * hd, count),
            (f"{p}/attn/q", (count,), h * hd, d, count),
            (f"{p}/attn/v", (count,), kv * hd, d, count)]


def ffn_groups(p: str, count: int, d: int, f: int):
    """A SwiGLU FFN of width ``f`` at ``p``: down, gate, up."""
    return [(f"{p}/down", (count,), d, f, count),
            (f"{p}/gate", (count,), f, d, count),
            (f"{p}/up", (count,), f, d, count)]


def groups(conf: dict):
    """Layer by layer, by name within a layer (the order decides between
    groups of equal shape and curve)."""
    m = model(conf)
    out = []
    for i, (_, count) in enumerate(segments(m)):
        p = f"segments/{i}"
        out += attn_groups(m, p, count) + ffn_groups(f"{p}/mlp", count,
                                                     m["d_model"], m["d_ff"])
    return out


def block_norm_leaves(m: dict, p: str, count: int):
    d, hd = m["d_model"], head_dim(m)
    return [(f"{p}/ln_attn", (count, d), "zeros", None),
            (f"{p}/ln_mlp", (count, d), "zeros", None),
            (f"{p}/attn/q_norm", (count, hd), "zeros", None),
            (f"{p}/attn/k_norm", (count, hd), "zeros", None)]


def leaves(conf: dict):
    m = model(conf)
    out = common.embed_leaves(conf)
    for i, (_, count) in enumerate(segments(m)):
        out += block_norm_leaves(m, f"segments/{i}", count)
    return out + common.factor_leaves(conf, groups(conf))


def attention_layer(x, w, ranks, p: str, l: int, m: dict, num):
    """Layer ``l`` of segment ``p`` up to the attention's residual."""
    h, kv, hd, eps = m["num_heads"], m["num_kv_heads"], head_dim(m), m["norm_eps"]
    s = x.shape[0]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]

    def lin(y, path):
        return num.lin(y, w[f"{path}/u"][l], w[f"{path}/v"][l], ranks[path])

    y = common.rms(x, w[f"{p}/ln_attn"][l], eps)
    q = common.rms(lin(y, f"{p}/attn/q").reshape(s, h, hd),
                   w[f"{p}/attn/q_norm"][l], eps)
    k = common.rms(lin(y, f"{p}/attn/k").reshape(s, kv, hd),
                   w[f"{p}/attn/k_norm"][l], eps)
    v = lin(y, f"{p}/attn/v").reshape(s, kv, hd)
    q, k = common.rope(q, pos, m["rope_base"]), common.rope(k, pos, m["rope_base"])
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    logit = jnp.einsum("shd,thd->hst", q, k, precision=num.precision)
    logit = logit.astype(jnp.float32) / math.sqrt(hd)
    prob = jax.nn.softmax(jnp.where(causal, logit, -1e30), -1)
    att = jnp.einsum("hst,thd->shd", num.cast(prob), v, precision=num.precision)
    return x + lin(att.reshape(s, h * hd), f"{p}/attn/o")


def ffn(y, w, ranks, p: str, l: int, num):
    """The SwiGLU FFN at ``p``, layer ``l``, of the normed input ``y``."""
    def lin(z, path):
        return num.lin(z, w[f"{path}/u"][l], w[f"{path}/v"][l], ranks[path])

    g = lin(y, f"{p}/gate")
    return lin(jax.nn.silu(g) * lin(y, f"{p}/up"), f"{p}/down")


def forward(w: dict, ranks: dict, tokens, conf: dict, dtype=jnp.float32,
            precision=common.HIGHEST):
    """Logits (S, V) of one sequence, every position, in ``dtype`` with
    matmuls at ``precision``."""
    m = model(conf)
    num = common.Numerics(dtype, precision)
    x = num.cast(w["embed"])[tokens]
    for i, (_, count) in enumerate(segments(m)):
        p = f"segments/{i}"
        for l in range(count):
            x = attention_layer(x, w, ranks, p, l, m, num)
            y = common.rms(x, w[f"{p}/ln_mlp"][l], m["norm_eps"])
            x = x + ffn(y, w, ranks, f"{p}/mlp", l, num)
    return common.head(x, w, conf, num)


def attention(conf: dict, serves=()) -> dict:
    m = model(conf, serves)
    hd = head_dim(m)
    return {"heads": m["num_heads"], "kv_heads": m["num_kv_heads"],
            "qk_dim": hd, "v_dim": hd, "kv_width": 2 * hd,
            "windows": [None] * sum(c for _, c in segments(m))}


def smoke(conf: dict) -> dict:
    """The repo's smoke widths (2 layers, d64, 4 heads, vocab 512), one
    layer a segment."""
    c = copy.deepcopy(conf)
    c["name"] = conf["name"] + "-smoke"
    c["model"].update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                      d_ff=128, vocab_size=512, layers_per_segment=1,
                      max_seq_len=256)
    return c
