"""Online activation second-moment accumulation (paper App. C.1, step 1).

DataSVD needs ``Sigma_l = X_l X_l^T`` for every factorized layer, where
``X_l in R^{n_l x N}`` stacks calibration activations column-wise. Storing
``X_l`` scales O(N * n_l); instead we batch-accumulate the unnormalized
covariance so memory is O(n_l^2), independent of the number of calibration
samples — exactly the scheme of Eq. (60) in the paper.

Accumulation is a pure pytree fold so it jit/pjit-s cleanly: on a mesh the
activations arrive batch-sharded and the ``psum`` inside ``accumulate`` (when
used under shard_map) or XLA's own all-reduce (when used under jit) produce
the global moment.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass
class CovarianceState:
    """Running unnormalized second moment for one layer input."""

    moment: Array  # (n, n) fp32
    count: Array  # () fp32 — number of activation vectors folded in

    @staticmethod
    def create(n: int) -> "CovarianceState":
        return CovarianceState(
            moment=jnp.zeros((n, n), jnp.float32),
            count=jnp.zeros((), jnp.float32),
        )


def accumulate(state: CovarianceState, x: Array) -> CovarianceState:
    """Fold a batch of activations into the running moment.

    ``x`` has shape (..., n); leading dims are flattened. Accumulation is in
    fp32 regardless of activation dtype (bf16 activations would lose the tail
    of the spectrum that DataSVD's whitening needs).
    """
    n = x.shape[-1]
    flat = x.reshape(-1, n).astype(jnp.float32)
    return CovarianceState(
        moment=state.moment + flat.T @ flat,
        count=state.count + jnp.asarray(flat.shape[0], jnp.float32),
    )


jax.tree_util.register_pytree_node(
    CovarianceState,
    lambda s: ((s.moment, s.count), None),
    lambda _, c: CovarianceState(*c),
)


def sqrt_and_inv_sqrt(moment: Array, count: Array | float, *, damping: float = 1e-6):
    """Symmetric square root and inverse square root of the (damped) moment.

    Returns ``(S, S_inv)`` with ``S = Sigma^{1/2}``. Damping regularizes
    directions never excited by the calibration set; the paper's whitening is
    otherwise singular for rank-deficient activation covariances.

    A numpy ``moment`` is solved on the host with numpy (see ``array_module``).
    """
    xp = array_module(moment)
    n = moment.shape[0]
    cov = moment / xp.maximum(xp.asarray(count, xp.float32), 1.0)
    # Scale-aware damping: relative to mean diagonal energy.
    lam = damping * (xp.trace(cov) / n + 1e-30)
    cov = cov + lam * xp.eye(n, dtype=cov.dtype)
    w, q = xp.linalg.eigh(cov)
    w = xp.maximum(w, 0.0) + lam
    s = (q * xp.sqrt(w)) @ q.T
    s_inv = (q * (1.0 / xp.sqrt(w))) @ q.T
    return s, s_inv


def array_module(x):
    """numpy for host arrays, else jax.numpy. DataSVD runs once at set-up,
    and for a TPU ``jnp.linalg.eigh``/``svd`` compile for minutes per shape
    (566 s for one 3072 x 3072 eigh for a v5e) while the host LAPACK solves
    the same problem in seconds; callers hand numpy in to get that."""
    return np if isinstance(x, np.ndarray) else jnp


def collect_layer_moments(apply_fn, params, batches, layer_taps) -> Dict[str, CovarianceState]:
    """Run calibration batches through ``apply_fn`` and accumulate per-tap moments.

    ``layer_taps`` maps tap name -> feature size. ``apply_fn(params, batch)``
    must return ``(outputs, taps)`` where ``taps[name]`` is the activation
    *input* to the corresponding linear layer. Used by the decomposition
    driver; kept dependency-free so tests can call it with toy closures.
    """
    states = {k: CovarianceState.create(n) for k, n in layer_taps.items()}

    @jax.jit
    def step(states, batch):
        _, taps = apply_fn(params, batch)
        return {k: accumulate(states[k], taps[k]) for k in states}

    for batch in batches:
        states = step(states, batch)
    return states
