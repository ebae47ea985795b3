"""Federated analytics: estimator accuracy + label-balance policy properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.analytics import bitagg, label_balance, normalization


def test_mean_estimate_unbiased():
    key = jax.random.PRNGKey(0)
    n, f = 50_000, 4
    true_means = jnp.asarray([0.2, -1.0, 2.5, 0.0])
    vals = true_means + 0.5 * jax.random.normal(key, (n, f))
    bits = bitagg.encode_mean_bits(vals, -4.0, 4.0, key, flip_prob=0.0)
    est = bitagg.estimate_mean(bits, -4.0, 4.0)
    np.testing.assert_allclose(np.asarray(est), np.asarray(true_means), atol=0.05)


@settings(deadline=None, max_examples=10)
@given(st.floats(0.05, 0.4), st.integers(0, 2 ** 31 - 1))
def test_randomized_response_debias(flip_prob, seed):
    """RR + debias recovers the mean (local DP costs variance, not bias)."""
    key = jax.random.PRNGKey(seed)
    n = 60_000
    vals = jnp.full((n, 1), 1.3)
    bits = bitagg.encode_mean_bits(vals, -4.0, 4.0, key, flip_prob=flip_prob)
    est = bitagg.estimate_mean(bits, -4.0, 4.0, flip_prob=flip_prob)
    assert float(est[0]) == pytest.approx(1.3, abs=0.12)


def test_estimate_variance_rejects_positional_bits():
    """Regression: a vestigial leading parameter used to swallow a caller's
    first positional argument silently — the bit tensors are now required
    keyword-only, so the misuse fails loudly."""
    key = jax.random.PRNGKey(2)
    vals = 0.3 + 0.1 * jax.random.normal(key, (30_000, 1))
    mb = bitagg.encode_mean_bits(vals, 0.0, 1.0, key)
    sb = bitagg.encode_mean_bits(jnp.square(vals), 0.0, 1.0,
                                 jax.random.fold_in(key, 1))
    var = bitagg.estimate_variance(mean_bits=mb, sq_bits=sb, lo=0.0, hi=1.0)
    assert float(var[0]) == pytest.approx(0.01, abs=0.004)
    with pytest.raises(TypeError):
        bitagg.estimate_variance(mb, sb)  # positional form must not exist
    with pytest.raises(TypeError):
        bitagg.estimate_variance(vals.shape, mean_bits=mb, sq_bits=sb)


def test_percentile_from_cdf():
    key = jax.random.PRNGKey(1)
    n = 40_000
    vals = jax.random.normal(key, (n, 1)) * 2.0 + 1.0  # N(1, 2)
    thr = jnp.linspace(-8.0, 10.0, 128)
    bits = bitagg.encode_threshold_bits(vals, thr, key)
    cdf = bitagg.estimate_cdf(bits)
    p50 = float(bitagg.percentile_from_cdf(cdf, thr, 0.5)[0])
    p90 = float(bitagg.percentile_from_cdf(cdf, thr, 0.9)[0])
    assert p50 == pytest.approx(1.0, abs=0.15)
    assert p90 == pytest.approx(1.0 + 2.0 * 1.2816, abs=0.25)


def test_cdf_monotone_under_rr_noise():
    key = jax.random.PRNGKey(2)
    vals = jax.random.normal(key, (500, 2))
    thr = jnp.linspace(-3, 3, 32)
    bits = bitagg.encode_threshold_bits(vals, thr, key, flip_prob=0.3)
    cdf = bitagg.estimate_cdf(bits, flip_prob=0.3)
    assert bool(jnp.all(jnp.diff(cdf, axis=-1) >= -1e-6))


def test_bisect_percentile():
    rs = np.random.RandomState(0)

    def sample_fn(rng):
        return jnp.asarray(rs.normal(2.0, 1.0, size=5000))

    med = bitagg.bisect_percentile(sample_fn, -10, 10, 0.5, rounds=12,
                                   rng=jax.random.PRNGKey(3))
    assert med == pytest.approx(2.0, abs=0.1)


def test_zscore_normalization_factors():
    from repro.data.synthetic import ClassifierTask
    task = ClassifierTask(num_features=8, seed=1)
    data = task.sample_devices(60_000, rng_seed=42)
    vals = jnp.asarray(data["features_raw"])
    lo, hi = -4000.0, 4000.0
    factors = normalization.learn_zscore(vals, lo, hi, jax.random.PRNGKey(4))
    true_mean, true_std = task.normalization_oracle()
    # bit-protocol variance is large for wide ranges; check correlation of
    # learned scale with true scale (what matters for conditioning)
    corr = np.corrcoef(factors.scale, true_std)[0, 1]
    assert corr > 0.95


# --- label balancing ----------------------------------------------------------
def test_label_ratio_estimate():
    key = jax.random.PRNGKey(5)
    labels = (jax.random.uniform(key, (80_000,)) < 0.07).astype(jnp.int32)
    est = label_balance.estimate_label_ratio(labels, key, flip_prob=0.2)
    assert est == pytest.approx(0.07, abs=0.02)


@settings(deadline=None, max_examples=30)
@given(st.floats(0.01, 0.99), st.floats(0.2, 0.8))
def test_dropoff_policy_hits_target(pos_ratio, target):
    """E[pos | kept] == target under the drop-off policy."""
    pol = label_balance.policy_from_ratio(pos_ratio, target)
    kept_pos = pol.keep_pos * pos_ratio
    kept_neg = pol.keep_neg * (1.0 - pos_ratio)
    achieved = kept_pos / (kept_pos + kept_neg)
    assert achieved == pytest.approx(target, abs=1e-6)
    assert 0 < pol.keep_pos <= 1.0 and 0 < pol.keep_neg <= 1.0
    # the minority class is never dropped
    if pos_ratio < target:
        assert pol.keep_pos == 1.0
    else:
        assert pol.keep_neg == 1.0


def test_apply_dropoff_weights():
    key = jax.random.PRNGKey(6)
    labels = (jax.random.uniform(key, (40_000,)) < 0.1).astype(jnp.float32)
    pol = label_balance.policy_from_ratio(0.1, 0.5)
    w = label_balance.apply_dropoff(labels, pol, jax.random.PRNGKey(77))
    kept_pos = float((w * labels).sum())
    kept_neg = float((w * (1 - labels)).sum())
    assert kept_pos / (kept_pos + kept_neg) == pytest.approx(0.5, abs=0.03)
