"""Pieces every architecture module shares: the numerics a forward runs
in, RMSNorm, RoPE, the truncated product of a FlexRank group, and the
weights outside the blocks."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import weights

HIGHEST = jax.lax.Precision.HIGHEST


class Numerics:
    """The dtype and matmul precision one forward runs in."""

    def __init__(self, dtype, precision):
        self.dtype, self.precision = dtype, precision

    def cast(self, a):
        return a.astype(self.dtype)

    def mm(self, a, b):
        return jnp.matmul(a, b, precision=self.precision)

    def lin(self, x, u, v, rank):
        """The rank-``rank`` truncated product ``x @ v[:, :r] @ u[:, :r].T``
        of full-rank factors ``u`` (d_out, R) and ``v`` (d_in, R)."""
        u, v = self.cast(u), self.cast(v)
        keep = self.cast(jnp.arange(u.shape[-1]) < rank)
        return self.mm(self.mm(x, v) * keep, u.T)


def rms(x, scale, eps):
    """``x * rsqrt(mean(x**2) + eps) * (1 + scale)``, in float32."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, pos, base):
    """RoPE over the whole last axis, ``base ** (-i / (D/2))`` on its two
    halves; ``x`` is (S, heads, D)."""
    half = x.shape[-1] // 2
    freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def embed_leaves(conf: dict):
    """The embedding, the final norm and an untied LM head."""
    m = conf["model"]
    d, v = m["d_model"], m["vocab_size"]
    out = [("embed", (v, d), "normal", embed_std(conf)),
           ("final_norm", (d,), "zeros", None)]
    if not m["tie_embeddings"]:
        out.append(("lm_head/w", (d, v), "normal", embed_std(conf)))
    return out


def embed_std(conf: dict) -> float:
    """The standard deviation of every Gaussian leaf that is no factor."""
    return (conf["weights"]["embed_std_times_sqrt_d"]
            / math.sqrt(conf["model"]["d_model"]))


def factor_leaves(conf: dict, groups):
    """The full-rank factors ``u`` and ``v`` of every group."""
    power = conf["weights"]["singular_value_power"]
    out = []
    for path, lead, d_out, d_in, _ in groups:
        r = min(d_out, d_in)
        scale = weights.factor_scale(r, d_out, power)
        out += [(f"{path}/u", tuple(lead) + (d_out, r), "factor", scale),
                (f"{path}/v", tuple(lead) + (d_in, r), "factor", scale)]
    return out


def head(x, w, conf: dict, num: Numerics):
    """The final norm and the LM head: float32 logits."""
    m = conf["model"]
    x = rms(x, w["final_norm"], m["norm_eps"])
    lm = (num.cast(w["embed"]).T if m["tie_embeddings"]
          else num.cast(w["lm_head/w"]))
    return num.mm(x, lm).astype(jnp.float32)
