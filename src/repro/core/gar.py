"""Gauge-Aligned Reparametrization (paper §3.5).

A rank-r factorization ``W_r = U_r V_r^T`` is gauge-free: for any invertible
``G``, ``(U_r G)(G^{-1} V_r^T)`` is the same matrix. GAR picks
``G = (U_r[rows, :])^{-1}`` for a set of r pivot rows so that ``U_r G`` has an
*identity block* on those rows. The identity is neither stored nor multiplied:

    z      = V_tilde^T x          # r x n  -> r
    y[rows]   = z                  # free
    y[other]  = U_hat @ z          # (m-r) x r

total ``O((m + n - r) r)`` FLOPs vs ``O(mn)`` dense and ``O((m+n) r)`` naive
low-rank — strictly cheaper than dense for every r < min(m, n).

The paper fixes rows = 1..r; we add partial-pivoting row selection (the gauge
is still exact) because ``U[1:r, :]`` can be near-singular for real models.
The row permutation is static metadata folded into the deploy-time params.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import scipy.linalg
import jax
import jax.numpy as jnp

Array = jax.Array


class GarFactors(NamedTuple):
    """Deployable GAR form of one layer at a fixed rank r.

    y = P^T [z ; u_hat @ z],  z = v_tilde^T @ x
    """

    u_hat: Array   # (m - r, r)
    v_tilde: Array  # (n, r)
    perm: Array    # (m,) int32 — output permutation (pivot rows first)

    @property
    def rank(self) -> int:
        return self.v_tilde.shape[1]


def _pivot_rows(u: np.ndarray) -> np.ndarray:
    """Greedy partial-pivoting row selection: r rows making U[rows] well-conditioned.

    The row permutation of an LU factorization with partial pivoting (LAPACK
    ``getrf``), whose first r rows are the pivots. O(m r^2).
    """
    m = u.shape[0]
    _, swaps = scipy.linalg.lu_factor(u.astype(np.float64), check_finite=False)
    rows = np.arange(m)
    for j, p in enumerate(swaps):       # LAPACK's row interchanges, in order
        rows[[j, p]] = rows[[p, j]]
    return rows


def gar_transform(u: Array, v: Array, r: int, *, pivot: bool = True) -> GarFactors:
    """Compute the GAR form of the rank-r truncation of (u, v).

    Host-side (numpy) — runs once per layer per deployment, O(r^3) for the
    inverse as in the paper.
    """
    u_r = np.asarray(u)[:, :r].astype(np.float64)
    v_r = np.asarray(v)[:, :r].astype(np.float64)
    m = u_r.shape[0]
    if pivot:
        rows = _pivot_rows(u_r)
    else:
        rows = np.arange(m)
    perm = np.concatenate([rows[:r], rows[r:]])
    u_p = u_r[perm]
    g = np.linalg.inv(u_p[:r])         # gauge G = U[rows,:]^{-1}; O(r^3), "negligible vs SVD"
    u_tilde = u_p @ g                  # top block == I_r by construction
    u_hat = u_tilde[r:]
    # W = U_r V_r^T = (U_r G)(G^{-1} V_r^T);  G^{-1} = U_p[:r]  =>  V_tilde = V_r (G^{-1})^T
    v_tilde = v_r @ u_p[:r].T
    return GarFactors(
        u_hat=jnp.asarray(u_hat, jnp.float32),
        v_tilde=jnp.asarray(v_tilde, jnp.float32),
        perm=jnp.asarray(perm, jnp.int32),
    )


def gar_apply(gar: GarFactors, x: Array) -> Array:
    """Reference forward ``y = W_r x`` for x of shape (..., n). O((m+n-r) r)."""
    z = x @ gar.v_tilde                       # (..., r)
    tail = z @ gar.u_hat.T                    # (..., m - r)
    y_perm = jnp.concatenate([z, tail], axis=-1)
    inv = jnp.argsort(gar.perm)
    return jnp.take(y_perm, inv, axis=-1)


def gar_flops(m: int, n: int, r: int, tokens: int = 1) -> int:
    """Theoretical MACs of the GAR forward (paper's O((m+n-r) r))."""
    return tokens * (n * r + (m - r) * r)


def lowrank_flops(m: int, n: int, r: int, tokens: int = 1) -> int:
    return tokens * (n * r + m * r)


def dense_flops(m: int, n: int, tokens: int = 1) -> int:
    return tokens * m * n


def reconstruction(gar: GarFactors) -> Array:
    """Dense W_r implied by the GAR form (tests/oracles)."""
    eye = jnp.eye(gar.rank, dtype=gar.v_tilde.dtype)
    u_tilde = jnp.concatenate([eye, gar.u_hat], axis=0)
    w_perm = u_tilde @ gar.v_tilde.T
    inv = jnp.argsort(gar.perm)
    return w_perm[inv]
