"""DP invariants (hypothesis property tests) + RDP accountant."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fl import dp
from repro.core.fl.accountant import (
    RDPAccountant, compute_epsilon, noise_for_epsilon, rdp_gaussian,
    rdp_subsampled_gaussian,
)


@settings(deadline=None, max_examples=40)
@given(st.integers(0, 2 ** 31 - 1), st.floats(0.01, 100.0),
       st.floats(0.1, 10.0))
def test_clipped_norm_never_exceeds_bound(seed, scale, clip):
    """Post-clip global norm <= clip for any update magnitude."""
    key = jax.random.PRNGKey(seed)
    tree = {"a": scale * jax.random.normal(key, (17,)),
            "b": {"c": scale * jax.random.normal(jax.random.fold_in(key, 1),
                                                 (3, 5))}}
    clipped, nrm, was_clipped = dp.clip_update(tree, clip)
    post = float(dp.global_norm(clipped))
    assert post <= clip * (1 + 1e-4)
    if float(nrm) <= clip:
        assert not bool(was_clipped)
        assert post == pytest.approx(float(nrm), rel=1e-5)


@settings(deadline=None, max_examples=20)
@given(st.integers(0, 2 ** 31 - 1))
def test_clip_preserves_direction(seed):
    key = jax.random.PRNGKey(seed)
    tree = {"w": 10.0 * jax.random.normal(key, (64,))}
    clipped, _, _ = dp.clip_update(tree, 1.0)
    cos = jnp.dot(tree["w"], clipped["w"]) / (
        jnp.linalg.norm(tree["w"]) * jnp.linalg.norm(clipped["w"]))
    assert float(cos) == pytest.approx(1.0, abs=1e-5)


def test_noise_stddev_semantics():
    from repro.configs.base import FLConfig
    fl = FLConfig(noise_multiplier=2.0, clip_norm=3.0)
    assert dp.noise_stddev(fl, 100, "tee") == pytest.approx(2.0 * 3.0 / 100)
    assert dp.noise_stddev(fl, 100, "device") == pytest.approx(2.0 * 3.0)


def test_add_noise_statistics():
    key = jax.random.PRNGKey(0)
    zeros = {"w": jnp.zeros((200_000,))}
    noised = dp.add_noise(zeros, key, 0.5)
    assert float(jnp.std(noised["w"])) == pytest.approx(0.5, rel=0.02)


# --- accountant -------------------------------------------------------------
def test_rdp_unsampled_matches_gaussian():
    assert rdp_subsampled_gaussian(1.0, 2.0, 8) == pytest.approx(
        rdp_gaussian(2.0, 8))


@settings(deadline=None, max_examples=25)
@given(st.floats(0.001, 0.5), st.floats(0.5, 8.0), st.integers(2, 64))
def test_subsampling_amplifies_privacy(q, sigma, alpha):
    """Subsampled RDP <= full-batch RDP, and monotone in q."""
    sub = rdp_subsampled_gaussian(q, sigma, alpha)
    full = rdp_gaussian(sigma, alpha)
    assert sub <= full + 1e-9
    assert rdp_subsampled_gaussian(q / 2, sigma, alpha) <= sub + 1e-12


def test_epsilon_monotone_in_rounds_and_noise():
    e1 = compute_epsilon(0.01, 1.0, 100, 1e-6)
    e2 = compute_epsilon(0.01, 1.0, 1000, 1e-6)
    e3 = compute_epsilon(0.01, 2.0, 1000, 1e-6)
    assert e1 < e2
    assert e3 < e2
    assert math.isfinite(e1)


def test_noise_for_epsilon_inverts():
    q, rounds, delta = 0.02, 500, 1e-6
    sigma = noise_for_epsilon(q, rounds, target_eps=4.0, delta=delta)
    assert compute_epsilon(q, sigma, rounds, delta) <= 4.0 + 1e-3
    # and not absurdly conservative
    assert compute_epsilon(q, sigma * 0.8, rounds, delta) > 4.0


def test_accountant_accumulates():
    acc = RDPAccountant()
    acc.step(0.01, 1.0, num_steps=10)
    e10 = acc.epsilon(1e-6)
    acc.step(0.01, 1.0, num_steps=90)
    e100 = acc.epsilon(1e-6)
    assert e100 > e10
    assert e100 == pytest.approx(compute_epsilon(0.01, 1.0, 100, 1e-6), rel=1e-6)
