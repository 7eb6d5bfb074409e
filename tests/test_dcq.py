"""DCQ estimator: correctness, efficiency (ARE ~ 0.955 claim), robustness."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dcq import (dcq, d_k, are_dcq, dcq_with_sigma,
                            quantile_levels)
from repro.core.robust_agg import (geometric_median_agg, median_agg,
                                   trimmed_mean_agg)


def test_quantile_levels():
    k = quantile_levels(10)
    np.testing.assert_allclose(np.asarray(k), np.arange(1, 11) / 11, rtol=1e-6)


def test_quantile_knots_are_host_constants_shared_with_kernel():
    """Reference and kernel compare against the same knots: correctly
    rounded normal quantiles, not a device's single-precision ndtri (off
    by ~170 ulp in the tails on a TPU, which flips CQ indicators)."""
    from statistics import NormalDist

    from repro.agg.kernel import cq_constants
    from repro.core.dcq import quantile_knots
    want = np.float32([NormalDist().inv_cdf(k / 11) for k in range(1, 11)])
    got = np.asarray(quantile_knots(10))
    assert got.dtype == np.float32 and (got == want).all()
    assert (np.float32(cq_constants(10)[0]) == got).all()


def test_dk_limit_is_pi_over_3():
    # K -> inf: D_K -> pi/3 (ARE -> 3/pi ~ 0.955). Paper §1.2(2).
    assert abs(d_k(200) - np.pi / 3) < 0.01
    assert abs(are_dcq(200) - 3 / np.pi) < 0.01


def test_dk_k10_close_to_paper_value():
    # at the paper's K=10 the ARE is already ~0.94
    assert 0.92 < are_dcq(10) < 0.96


def test_dcq_unbiased_normal():
    key = jax.random.PRNGKey(0)
    m, p = 4001, 3
    mu = jnp.array([1.0, -2.0, 0.5])
    sd = 2.0
    vals = mu + sd * jax.random.normal(key, (m, p))
    est = dcq(vals, jnp.full((p,), sd), K=10)
    np.testing.assert_allclose(np.asarray(est), np.asarray(mu), atol=0.15)


def test_dcq_variance_reduction_vs_median():
    """Empirical ARE of DCQ should beat the median's 0.637 decisively."""
    key = jax.random.PRNGKey(1)
    reps, m = 400, 501
    vals = jax.random.normal(key, (reps, m))
    scale = jnp.ones((reps,))
    est_dcq = jax.vmap(lambda v, s: dcq(v[:, None], s[None], K=10)[0])(vals, scale)
    est_med = jnp.median(vals, axis=1)
    est_mean = jnp.mean(vals, axis=1)
    var_ratio_dcq = float(jnp.var(est_mean) / jnp.var(est_dcq))
    var_ratio_med = float(jnp.var(est_mean) / jnp.var(est_med))
    assert var_ratio_dcq > 0.85          # ~0.94 expected at K=10
    assert var_ratio_med < 0.75          # ~0.64 expected
    assert var_ratio_dcq > var_ratio_med + 0.1


def test_dcq_with_sigma_matches_dk():
    est, sd = dcq_with_sigma(jnp.zeros((100, 2)) + 1.0, jnp.ones((2,)), K=10)
    expect = np.sqrt(d_k(10)) / np.sqrt(100)
    np.testing.assert_allclose(np.asarray(sd), expect, rtol=1e-5)


def test_dcq_robust_to_byzantine_scaling():
    """10% of machines send -3x values (paper's attack): DCQ barely moves."""
    key = jax.random.PRNGKey(2)
    m = 500
    vals = 5.0 + jax.random.normal(key, (m, 1))
    n_byz = 50
    vals = vals.at[:n_byz].set(-3.0 * vals[:n_byz])
    est = dcq(vals, jnp.ones((1,)), K=10)
    assert abs(float(est[0]) - 5.0) < 0.35
    # mean is destroyed
    assert abs(float(vals.mean()) - 5.0) > 1.5


def test_trimmed_mean_and_geomedian():
    key = jax.random.PRNGKey(3)
    vals = 2.0 + jax.random.normal(key, (200, 4))
    vals = vals.at[:20].set(100.0)
    tm = trimmed_mean_agg(vals, beta=0.3)
    gm = geometric_median_agg(vals)
    md = median_agg(vals)
    for est in (tm, gm, md):
        np.testing.assert_allclose(np.asarray(est), 2.0, atol=0.5)


def test_dcq_axis_argument():
    key = jax.random.PRNGKey(4)
    vals = jax.random.normal(key, (3, 101, 2))
    a = dcq(jnp.moveaxis(vals, 1, 0), jnp.ones((3, 2)), K=5)
    b = dcq(vals, jnp.ones((3, 2)), K=5, axis=1)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
