"""§4 SPSD approximation: Algorithm 2 vs baselines (Theorem 3 claims)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    fast_spsd_wang,
    faster_spsd,
    nystrom,
    optimal_core,
    rbf_kernel_oracle,
    spsd_error_ratio,
)
from repro.data import clustered_points, tune_rbf_sigma


@pytest.fixture(scope="module")
def kernel_setup():
    n, d, k = 600, 24, 15
    X = clustered_points(jax.random.key(0), n, d, n_clusters=10, spread=0.6)
    sigma = tune_rbf_sigma(X, k=k, target_eta=0.75)
    oracle = rbf_kernel_oracle(X, sigma)
    return n, oracle, oracle(None, None)


def _mean_err(fn, K, trials=3):
    return float(np.mean([float(spsd_error_ratio(K, fn(jax.random.key(31 * t)))) for t in range(trials)]))


@pytest.mark.slow
def test_alg2_close_to_optimal_at_s10c(kernel_setup):
    """§6.2: faster-SPSD ≈ optimal once s = 10c."""
    n, oracle, K = kernel_setup
    c = 30
    ours = _mean_err(lambda k: faster_spsd(k, oracle, n, c, 10 * c), K)
    opt = _mean_err(lambda k: optimal_core(k, oracle, n, c), K)
    assert ours < opt * 1.10, (ours, opt)


def test_alg2_beats_wang16_at_small_s(kernel_setup):
    """Table 7 pattern: fast-SPSD (Wang'16b) much worse at small s."""
    n, oracle, K = kernel_setup
    c, s = 30, 8 * 30
    ours = _mean_err(lambda k: faster_spsd(k, oracle, n, c, s), K)
    wang = _mean_err(lambda k: fast_spsd_wang(k, oracle, n, c, s), K)
    assert ours < wang, (ours, wang)


def test_alg2_beats_nystrom(kernel_setup):
    n, oracle, K = kernel_setup
    c = 30
    ours = _mean_err(lambda k: faster_spsd(k, oracle, n, c, 10 * c), K, trials=4)
    nys = _mean_err(lambda k: nystrom(k, oracle, n, c), K, trials=4)
    assert ours <= nys * 1.02, (ours, nys)


def test_core_is_psd(kernel_setup):
    n, oracle, K = kernel_setup
    res = faster_spsd(jax.random.key(5), oracle, n, 30, 200)
    ev = jnp.linalg.eigvalsh(0.5 * (res.X + res.X.T))
    assert float(ev.min()) > -1e-4


def test_entry_observation_accounting(kernel_setup):
    """Theorem 3 / Table 4: exact entry counts for all four batch paths."""
    n, oracle, K = kernel_setup
    c, s = 30, 150
    assert faster_spsd(jax.random.key(6), oracle, n, c, s).entries_observed == n * c + s * s
    assert nystrom(jax.random.key(7), oracle, n, c).entries_observed == n * c
    assert fast_spsd_wang(jax.random.key(8), oracle, n, c, s).entries_observed == n * c + s * s
    assert optimal_core(jax.random.key(9), oracle, n, c).entries_observed == n * n


# ---------------------------------------------------------------------------
# input validation + edge cases (rank-deficient kernels, duplicate samples)
# ---------------------------------------------------------------------------


def test_sample_size_validation(kernel_setup):
    """c > n (or c ≤ 0, s ≤ 0) must fail with a clear ValueError, not the
    opaque shape error jax.random.choice(replace=False) raises."""
    n, oracle, _ = kernel_setup
    for fn in (
        lambda: nystrom(jax.random.key(0), oracle, n, n + 1),
        lambda: optimal_core(jax.random.key(0), oracle, n, 0),
        lambda: fast_spsd_wang(jax.random.key(0), oracle, n, n + 5, 100),
        lambda: faster_spsd(jax.random.key(0), oracle, n, -1, 100),
    ):
        with pytest.raises(ValueError, match="0 < c <= n"):
            fn()
    for fn in (
        lambda: fast_spsd_wang(jax.random.key(0), oracle, n, 10, 0),
        lambda: faster_spsd(jax.random.key(0), oracle, n, 10, -3),
    ):
        with pytest.raises(ValueError, match="s > 0"):
            fn()


def test_rank_deficient_kernel_duplicated_points():
    """Duplicated data points make K (and any sampled C) exactly
    rank-deficient; every batch path must stay finite with a sane fit."""
    n, d = 300, 16
    X = clustered_points(jax.random.key(40), n, d, n_clusters=8, spread=0.5)
    X = X.at[50:100].set(X[0])  # 51 identical points
    sigma = tune_rbf_sigma(X, k=10, target_eta=0.75)
    oracle = rbf_kernel_oracle(X, sigma)
    K = oracle(None, None)
    c, s = 24, 120
    for fn in (
        lambda k: nystrom(k, oracle, n, c),
        lambda k: optimal_core(k, oracle, n, c),
        lambda k: fast_spsd_wang(k, oracle, n, c, s),
        lambda k: faster_spsd(k, oracle, n, c, s),
    ):
        res = fn(jax.random.key(41))
        assert bool(jnp.all(jnp.isfinite(res.X))), fn
        err = float(spsd_error_ratio(K, res))
        assert np.isfinite(err) and err < 1.0, (fn, err)


def test_duplicate_leverage_samples_survive(kernel_setup):
    """s ≫ n forces duplicate sampled indices in S₁/S₂ (sampling is with
    replacement) and near-duplicate rows in the sketched operands; the
    floored solves must stay finite and the PSD projection must hold for
    both leverage-sampling paths."""
    n, oracle, K = kernel_setup
    c, s = 30, 2 * n  # pigeonhole: every index set has duplicates
    for fn in (
        lambda k: fast_spsd_wang(k, oracle, n, c, s),
        lambda k: faster_spsd(k, oracle, n, c, s),
    ):
        res = fn(jax.random.key(42))
        assert bool(jnp.all(jnp.isfinite(res.X)))
        ev = jnp.linalg.eigvalsh(0.5 * (res.X + res.X.T))
        assert float(ev.min()) > -1e-4
        assert float(spsd_error_ratio(K, res)) < 1.0
