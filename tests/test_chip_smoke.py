"""chip_smoke.py: its refusal off the chip, and its phases at a small width.

The phases run here on a cut-down whisper config with the Pallas kernels in
interpret mode (the test passes ``use_pallas=True`` to the async engines and
steers the sync round's ``on_tpu`` switch; on the chip the backend decides),
so the checks the chip run relies on
— the f32 survivor reference and its bound, masked-vs-unmasked bit
identity, the tier against the single host — are exercised on every PR.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load()


@pytest.fixture(scope="module")
def setup(smoke):
    from repro.configs import registry

    cfg = registry.get_config("whisper-tiny", reduced=True).with_overrides(
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=64, num_layers=1, num_encoder_layers=1, encoder_seq=8,
        max_seq_len=32)
    fl = smoke.fl_config()
    model, params, batch = smoke.make_setup(cfg, seed=0,
                                            n_clients=smoke.BUFFER)
    deltas, fields = smoke.client_phase(model, params, batch, fl)
    return cfg, fl, model, params, batch, deltas, fields


def test_refuses_without_a_tpu():
    """On a CPU backend the script exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, env=env, timeout=120)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no TPU" in out.stderr and "cpu" in out.stderr


def test_client_phase_yields_deltas_inside_the_clip_ball(smoke, setup):
    _, fl, _, params, _, deltas, fields = setup
    assert fields["clients"] == len(deltas) == smoke.BUFFER
    for d in deltas:
        nrm = sum(float((x * x).sum()) for x in jax.tree.leaves(d)) ** 0.5
        assert nrm == pytest.approx(0.5 * fl.clip_norm, rel=1e-4)


def test_fedbuff_phases_hold_reference_and_bit_identity(smoke, setup):
    _, fl, _, params, _, deltas, _ = setup
    lines = []
    smoke.fedbuff_phases(params, deltas, fl, use_pallas=True,
                         expect_kernels=False, emit=lines.append)
    phases = [s for s in lines if s.startswith("phase fedbuff_")]
    assert len(phases) == 5
    for s in phases:
        assert "use_pallas=True" in s and "flush1_err_over_bound" in s
    assert sum(s.startswith("bitcmp") for s in lines) == 3


def test_reference_check_fails_on_a_wrong_release(smoke, setup):
    """The bound is tight enough to catch one contribution too many."""
    _, _, _, params, _, deltas, _ = setup
    wrong = jax.tree.map(lambda p, a, b: p + (a + b) / 2, params,
                         deltas[0], deltas[1])
    with pytest.raises(smoke.PhaseFailed):
        smoke.check_reference("flush", wrong, params, deltas[:3], 1e8)


def test_sync_phase_masked_equals_unmasked(smoke, setup, monkeypatch):
    """The round's own backend switch is steered onto the kernel path
    (interpret mode here), as it runs on the chip."""
    from repro.core.fl import round as fl_round

    _, fl, model, params, batch, deltas, _ = setup
    monkeypatch.setattr(fl_round, "on_tpu", lambda: True)
    fields = smoke.sync_phase(model, params, batch, deltas, fl,
                              expect_kernels=False)
    assert fields["use_pallas"] is True
    assert fields["round1_masked_eq_unmasked"] is True
    assert fields["encode_pallas_eq_jnp"] is True


def test_sketch_phase_pallas_equals_jnp(smoke, setup):
    _, fl, _, params, _, deltas, _ = setup
    fields = smoke.sketch_phase(params, deltas, fl, use_pallas=True,
                                expect_kernels=False)
    assert fields["pallas_eq_jnp"] is True


def test_tier_phase_dead_leaf_matches_single_host(smoke, setup):
    """Multiplexed onto this process's devices; the four-chip run puts one
    leaf on each chip."""
    _, fl, _, params, _, _, _ = setup
    deltas = smoke.random_deltas(params, fl, 8, seed=0)
    lines = []
    fields = smoke.tier_phase(params, deltas, fl, use_pallas=True,
                              expect_kernels=False, emit=lines.append)
    assert fields["flush_err_over_bound"] <= 1.0
    assert any(s.startswith("bitcmp tier") for s in lines)
