"""Unit tests for the FlexRank core: DataSVD, DP selection, GAR, profiles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # optional dev dependency: property tests skip cleanly
    def settings(**_kw):
        return lambda f: f

    def given(*_a, **_k):
        def deco(f):
            def _skipped():
                pytest.skip("hypothesis not installed (optional dev extra)")
            _skipped.__name__ = f.__name__
            _skipped.__doc__ = f.__doc__
            return _skipped
        return deco

    class st:  # noqa: N801 - mirrors hypothesis.strategies namespace
        integers = staticmethod(lambda *a, **k: None)

from repro.core import (CovarianceState, accumulate, brute_force_selection,
                        datasvd_factors, dp_rank_selection, gar_apply,
                        gar_transform, make_layer_candidates, plain_svd_factors,
                        select_profiles, truncation_error_curve, uniform_table)
from repro.core.datasvd import reconstruction_error
from repro.core.gar import dense_flops, gar_flops, lowrank_flops, reconstruction
from repro.core.profiles import ProfileTable, rank_mask


# ----------------------------------------------------------------- DataSVD

def _correlated_acts(rng, n, num, cond=50.0):
    scales = np.linspace(1.0, cond, n)
    return (rng.standard_normal((num, n)) * scales).astype(np.float32)


def test_datasvd_beats_plain_svd_on_correlated_data(rng):
    """The whole point of Eq. (3): lower *output* error at equal rank."""
    w = rng.standard_normal((24, 16)).astype(np.float32)
    x = _correlated_acts(rng, 16, 512)
    st_ = accumulate(CovarianceState.create(16), jnp.asarray(x))
    f_data = datasvd_factors(jnp.asarray(w), st_.moment, st_.count)
    f_plain = plain_svd_factors(jnp.asarray(w))
    for r in (2, 4, 8):
        err_d = np.mean(np.square((w - np.asarray(f_data.reconstruct(r))) @ x.T))
        err_p = np.mean(np.square((w - np.asarray(f_plain.reconstruct(r))) @ x.T))
        assert err_d <= err_p * 1.001, (r, err_d, err_p)


def test_datasvd_full_rank_exact(rng):
    w = rng.standard_normal((12, 10)).astype(np.float32)
    x = _correlated_acts(rng, 10, 256)
    st_ = accumulate(CovarianceState.create(10), jnp.asarray(x))
    f = datasvd_factors(jnp.asarray(w), st_.moment, st_.count)
    assert np.abs(w - np.asarray(f.reconstruct())).max() < 1e-3


def test_datasvd_host_matches_jnp(rng):
    """numpy operands run the same factorization on the host (how
    ``decompose`` calls it) and agree with the jnp path."""
    w = rng.standard_normal((12, 10)).astype(np.float32)
    x = _correlated_acts(rng, 10, 256)
    moment = x.T @ x
    dev = datasvd_factors(jnp.asarray(w), jnp.asarray(moment), 256.0)
    host = datasvd_factors(w, moment, 256.0)
    assert isinstance(host.u, np.ndarray) and isinstance(host.v, np.ndarray)
    scale = np.abs(w).max()
    np.testing.assert_allclose(host.reconstruct(4), np.asarray(
        dev.reconstruct(4)), atol=1e-4 * scale)
    plain_dev, plain_host = plain_svd_factors(jnp.asarray(w)), \
        plain_svd_factors(w)
    np.testing.assert_allclose(plain_host.reconstruct(4), np.asarray(
        plain_dev.reconstruct(4)), atol=1e-4 * scale)


def test_truncation_curve_monotone(rng):
    w = rng.standard_normal((16, 12)).astype(np.float32)
    x = _correlated_acts(rng, 12, 256)
    st_ = accumulate(CovarianceState.create(12), jnp.asarray(x))
    f = datasvd_factors(jnp.asarray(w), st_.moment, st_.count)
    curve = np.asarray(truncation_error_curve(jnp.asarray(w), f, st_.moment))
    assert np.all(np.diff(curve) <= 1e-4)
    assert curve[-1] < 1e-5


def test_covariance_accumulate_is_linear(rng):
    x = rng.standard_normal((64, 8)).astype(np.float32)
    st1 = accumulate(CovarianceState.create(8), jnp.asarray(x))
    st2 = accumulate(accumulate(CovarianceState.create(8), jnp.asarray(x[:32])),
                     jnp.asarray(x[32:]))
    np.testing.assert_allclose(np.asarray(st1.moment), np.asarray(st2.moment),
                               rtol=1e-5)
    assert float(st1.count) == float(st2.count) == 64.0


# ------------------------------------------------------------ DP selection

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 5), st.integers(2, 5), st.integers(0, 10_000))
def test_dp_matches_bruteforce_pareto(n_layers, n_levels, seed):
    rng = np.random.default_rng(seed)
    cands = []
    for _ in range(n_layers):
        curve = np.sort(rng.random(8))[::-1].cumsum()[::-1]
        cands.append(make_layer_candidates(curve, 7.0, num_levels=n_levels))
    chain = dp_rank_selection(cands)
    bf = brute_force_selection(cands)
    # every chain point must be Pareto-optimal wrt brute force
    for p in chain:
        assert not any(q.saving >= p.saving and q.error < p.error - 1e-9 for q in bf), p
    # nestedness
    for a, b in zip(chain, chain[1:]):
        assert all(x <= y for x, y in zip(a.ranks, b.ranks))


def test_select_profiles_respects_budget():
    curve = np.asarray([4.0, 2.0, 1.0, 0.0])
    cands = [make_layer_candidates(curve, 10.0, num_levels=4) for _ in range(3)]
    chain = dp_rank_selection(cands)
    total = 3 * 4 * 10.0
    for b in (0.3, 0.6, 1.0):
        (p,) = select_profiles(chain, [b], total)
        assert total - p.saving <= b * total + 1e-6


# --------------------------------------------------------------------- GAR

@settings(max_examples=20, deadline=None)
@given(st.integers(6, 24), st.integers(5, 20), st.integers(0, 1000))
def test_gar_exactness(m, n, seed):
    rng = np.random.default_rng(seed)
    k = min(m, n)
    r = max(1, k // 2)
    u = rng.standard_normal((m, k)).astype(np.float32)
    v = rng.standard_normal((n, k)).astype(np.float32)
    g = gar_transform(jnp.asarray(u), jnp.asarray(v), r)
    w_r = u[:, :r] @ v[:, :r].T
    np.testing.assert_allclose(np.asarray(reconstruction(g)), w_r,
                               rtol=2e-3, atol=2e-3)
    x = rng.standard_normal((4, n)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(gar_apply(g, jnp.asarray(x))),
                               x @ w_r.T, rtol=2e-3, atol=2e-3)


def test_gar_flops_strictly_below_dense():
    for m, n in ((512, 512), (1024, 256), (300, 700)):
        for r in range(1, min(m, n), max(1, min(m, n) // 7)):
            assert gar_flops(m, n, r) < dense_flops(m, n)
            assert gar_flops(m, n, r) < lowrank_flops(m, n, r)


def test_gar_handles_illconditioned_top_block(rng):
    # first r rows of U nearly singular -> pivoting must save the inverse
    u = rng.standard_normal((16, 8)).astype(np.float64)
    u[:4] = 1e-9 * rng.standard_normal((4, 8))
    v = rng.standard_normal((12, 8)).astype(np.float64)
    g = gar_transform(jnp.asarray(u), jnp.asarray(v), 4)
    w_r = (u[:, :4] @ v[:, :4].T).astype(np.float32)
    np.testing.assert_allclose(np.asarray(reconstruction(g)), w_r, atol=1e-3)


@pytest.mark.parametrize("m,r", [(16, 8), (40, 40), (64, 5)])
def test_gar_pivots_are_partial_pivoting_rows(rng, m, r):
    """The LU-based pivot search picks the rows of textbook Gaussian
    elimination with partial pivoting, in order."""
    from repro.core.gar import _pivot_rows
    u = rng.standard_normal((m, r))
    work, rows = u.copy(), np.arange(m)
    for j in range(r):
        p = j + int(np.argmax(np.abs(work[j:, j])))
        work[[j, p]], rows[[j, p]] = work[[p, j]], rows[[p, j]]
        work[j + 1:] -= np.outer(work[j + 1:, j] / work[j, j], work[j])
    np.testing.assert_array_equal(_pivot_rows(u), rows)


# ---------------------------------------------------------------- profiles

def test_profile_table_asserts_nested():
    with pytest.raises(AssertionError):
        ProfileTable(("a",), np.asarray([[4], [2]], np.int32), (0.5, 1.0), (4,))


def test_uniform_table_nested_and_capped():
    t = uniform_table(["a", "b"], [10, 6], [0.3, 0.7, 1.0])
    assert np.all(np.diff(t.table, axis=0) >= 0)
    assert np.all(t.table[-1] == [10, 6])


@given(st.integers(1, 64), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_rank_mask_counts(rank, full):
    rank = min(rank, full)
    m = np.asarray(rank_mask(rank, full))
    assert m.sum() == rank
    assert np.all(m[:rank] == 1) and np.all(m[rank:] == 0)
