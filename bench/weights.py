"""Weights from the seed, shared by the state maker and the plain reference.

Both sides name the same leaves by path and shape and draw them here, so
the reference can rebuild every weight from the seed without taking
anything the program made. A leaf's key is the seed's key folded with a
hash of its path: the draw does not depend on the order of the leaves.

Kinds of leaf:

* ``("factor", scale)``: a FlexRank factor ``u`` (..., d_out, R) or ``v``
  (..., d_in, R); column ``j`` is Gaussian of unit norm times ``scale[j]``;
* ``("normal", std)``: Gaussian (the embedding, an untied LM head);
* ``("zeros", None)``: zeros (the RMSNorm scales, applied as ``1 + s``).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words from a seed of any size (beyond 32 bits too)."""
    return np.random.SeedSequence(int(seed)).generate_state(2).astype(np.uint32)


def root_key(seed: int) -> jax.Array:
    return jax.random.wrap_key_data(jnp.asarray(seed_words(seed)),
                                    impl="threefry2x32")


def singular_values(full_rank: int, d_out: int, power: float) -> np.ndarray:
    """The stated profile: ``s_j ~ (j + 1) ** -power``, sum of squares d_out,
    so a layer keeps the scale of its normalised input."""
    s = np.arange(1, full_rank + 1, dtype=np.float64) ** -power
    return s * math.sqrt(d_out / float(np.sum(s * s)))


def tail_curve(s: np.ndarray, layers: int) -> np.ndarray:
    """``curve[r - 1]``: energy left out when keeping rank r, summed over
    the group's layers (the DP's probe error as ``FR.decompose`` defines
    it, here in closed form from the profile)."""
    sq = s * s
    tail = np.concatenate([np.cumsum(sq[::-1])[::-1][1:], [0.0]])
    return tail * layers


def factor_scale(full_rank: int, d_out: int, power: float) -> np.ndarray:
    """Column scale of both factors: ``u v^T`` then has singular values
    ``s_j`` (up to the random columns' departure from orthonormality)."""
    return np.sqrt(singular_values(full_rank, d_out, power)).astype(np.float32)


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def draw(seed: int, leaves):
    """``leaves``: (path, shape, kind, arg) tuples. Returns {path: array},
    float32 on the default device, from one jitted call. Leaves of one
    shape, kind and scale are drawn by one vmapped draw over their keys
    (the same numbers as one draw each, and a program that compiles in a
    fraction of the time)."""
    groups = {}
    for path, shape, kind, arg in sorted(leaves, key=lambda x: x[0]):
        sig = (tuple(shape), kind,
               None if arg is None else np.asarray(arg, np.float32).tobytes())
        groups.setdefault(sig, []).append((path, arg))
    return _draw_fn(tuple((sig, tuple(p for p, _ in members))
                          for sig, members in groups.items()))(root_key(seed))


_DRAWS = {}


def _draw_fn(plan):
    """The jitted draw of one plan, made once per process."""
    if plan in _DRAWS:
        return _DRAWS[plan]

    def body(key):
        out = {}
        for (shape, kind, arg), paths in plan:
            if kind == "zeros":
                out.update({p: jnp.zeros(shape, jnp.float32) for p in paths})
                continue
            keys = jnp.stack([_path_key(key, p) for p in paths])
            x = jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(
                keys)
            if kind == "factor":
                x = x * (jnp.asarray(np.frombuffer(arg, np.float32))
                         / math.sqrt(shape[-2]))
            elif kind == "normal":
                x = x * float(np.frombuffer(arg, np.float32)[0])
            else:
                raise ValueError(f"unknown leaf kind {kind!r}")
            out.update({p: x[i] for i, p in enumerate(paths)})
        return out

    _DRAWS[plan] = jax.jit(body)
    return _DRAWS[plan]
