"""DataSVD: activation-aware low-rank factorization (paper §3.1 + App. C.1).

Given a layer weight ``W in R^{m x n}`` (acting as ``y = W x``) and the
activation second moment ``Sigma = X X^T``, solve

    min_{U,V} E ||(W - U V^T) x||^2  =  ||(W - U V^T) Sigma^{1/2}||_F^2

in closed form: SVD the whitened weight ``W Sigma^{1/2} = P Lambda Q^T`` and
set ``U = P Lambda^{1/2}``, ``V = Sigma^{-1/2} Q Lambda^{1/2}`` (Eq. 61).
Truncating the factor columns to the first r is then *optimal in the
data-weighted metric* and the columns are importance-ordered — the property
the DP search and nested training rely on.

``plain_svd_factors`` (Sigma = I) is kept as the paper's SVD baseline.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.covariance import array_module, sqrt_and_inv_sqrt

Array = jax.Array


class Factors(NamedTuple):
    """Importance-ordered factorization W ~= U @ V.T (columns ordered)."""

    u: Array  # (m, r)
    v: Array  # (n, r)

    @property
    def rank(self) -> int:
        return self.u.shape[-1]

    def reconstruct(self, r: Optional[int] = None) -> Array:
        if r is None:
            return self.u @ self.v.T
        return self.u[:, :r] @ self.v[:, :r].T


def datasvd_factors(
    w: Array,
    moment: Array,
    count: Array | float,
    *,
    max_rank: Optional[int] = None,
    damping: float = 1e-6,
) -> Factors:
    """Whitened SVD factorization of ``w`` against activation moment; on the
    host in numpy when both are numpy arrays (``array_module``)."""
    xp = array_module(moment)
    w = w.astype(xp.float32)
    s, s_inv = sqrt_and_inv_sqrt(moment, count, damping=damping)
    p, lam, qt = xp.linalg.svd(w @ s, full_matrices=False)
    q = qt.T
    if max_rank is not None:
        p, lam, q = p[:, :max_rank], lam[:max_rank], q[:, :max_rank]
    sqrt_lam = xp.sqrt(lam)
    u = p * sqrt_lam[None, :]
    v = (s_inv @ q) * sqrt_lam[None, :]
    return Factors(u=u, v=v)


def plain_svd_factors(w: Array, *, max_rank: Optional[int] = None) -> Factors:
    """Weight-only SVD baseline (no activation weighting)."""
    xp = array_module(w)
    w = w.astype(xp.float32)
    p, lam, qt = xp.linalg.svd(w, full_matrices=False)
    q = qt.T
    if max_rank is not None:
        p, lam, q = p[:, :max_rank], lam[:max_rank], q[:, :max_rank]
    sqrt_lam = xp.sqrt(lam)
    return Factors(u=p * sqrt_lam[None, :], v=q * sqrt_lam[None, :])


def reconstruction_error(w: Array, factors: Factors, r: int, moment: Array | None = None) -> Array:
    """Data-weighted (or plain) Frobenius error of the rank-r truncation.

    With ``moment`` given this is the probe error the DP consumes:
    ``||(W - U_r V_r^T) Sigma^{1/2}||_F^2 / trace`` — normalized so errors are
    comparable across layers of different width.
    """
    delta = w.astype(jnp.float32) - factors.reconstruct(r)
    if moment is None:
        return jnp.sum(delta * delta)
    # tr(d Sigma d^T); Sigma unnormalized is fine — normalization cancels in
    # the DP's relative comparisons but we normalize for numerical hygiene.
    sig = moment / jnp.maximum(jnp.trace(moment), 1e-30)
    return jnp.einsum("ij,jk,ik->", delta, sig, delta)


def truncation_error_curve(w: Array, factors: Factors, moment: Array | None = None) -> Array:
    """Vector of data-weighted errors for every truncation rank r=1..R.

    Cheap closed form: in the whitened metric the error of rank-r truncation is
    the tail energy ``sum_{i>r} lambda_i^2``. We recompute from factors to stay
    correct for any (possibly post-hoc) factor pair, not only exact SVDs.
    """
    if moment is None:
        # Plain Frobenius tail energies via Gram trick (works for orthogonal
        # column structure from SVD; for general factors fall back to direct).
        lam2 = jnp.sum(factors.u * factors.u, axis=0) * jnp.sum(factors.v * factors.v, axis=0)
        total = jnp.sum(lam2)
        return total - jnp.cumsum(lam2)
    errs = [reconstruction_error(w, factors, r, moment) for r in range(1, factors.rank + 1)]
    return jnp.stack(errs)
