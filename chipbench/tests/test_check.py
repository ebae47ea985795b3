"""The correctness check: a sound run passes it, and every fault a cell can
have fails it.  Each case drives a whole run on the CPU at a tiny size (the
harness's look for a chip skipped) with the timed path broken underneath."""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import drive, harness, reference
from chipbench.control import session_weights

CHECKOUT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 4242
ONE_CHIP = ["papaya.backlog", "secagg.dropout10", "secagg.full"]


def _wrap_flush(eng, fault):
    """Make both flush programs of an engine return ``fault(args, out)``."""
    def wrap(step):
        def bad(*args):
            return fault(args, step(*args))
        return bad
    eng._step = wrap(eng._step)
    build = eng._build_flush_step
    eng._build_flush_step = lambda: wrap(build())


def state_unchanged(args, out):
    params, opt_state = args[0], args[1]
    return params, opt_state, out[2]


def answer_altered(args, out):
    new, opt, metrics = out
    leaves, treedef = jax.tree.flatten(new)
    leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1e-4)
    return jax.tree.unflatten(treedef, leaves), opt, metrics


def half_left_out(eng):
    """Store only the even slots' rows and weights: the flush then takes
    the mean over the rest."""
    write = eng._write_row

    def bad(bufs, stal, wts, norms, clips, slot, *rest):
        if int(slot) % 2:
            return bufs, stal, wts, norms, clips
        return write(bufs, stal, wts, norms, clips, slot, *rest)

    eng._write_row = bad


FAULTS = {
    "state_unchanged": lambda eng: _wrap_flush(eng, state_unchanged),
    "answer_altered": lambda eng: _wrap_flush(eng, answer_altered),
    "half_left_out": half_left_out,
}


@pytest.fixture
def faulty(monkeypatch):
    def install(fault):
        build = drive.build_engine

        def build_faulty(config, params, telemetry):
            eng = build(config, params, telemetry)
            FAULTS[fault](eng)
            return eng

        monkeypatch.setattr(drive, "build_engine", build_faulty)
    return install


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_sound_run_is_correct(workload, tiny_cell, run_tiny):
    out = run_tiny(tiny_cell(workload), SEED)
    limit = out["check"]["err_over_bound"][1]
    assert out["correct"], out["check"]
    assert out["attempted"] >= 2 and out["failed"] == 0
    assert 0 < out["check"]["err_over_bound"][0] <= limit
    assert list(out)[-1] == "check"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_fault_is_not_correct(workload, fault, tiny_cell, run_tiny, faulty):
    faulty(fault)
    out = run_tiny(tiny_cell(workload), SEED)
    assert not out["correct"], (fault, out["check"])
    assert out["failed"] >= 1


@pytest.mark.parametrize("fault", ["sound", "exchange_left_out",
                                   "state_unchanged"])
def test_tier_on_four_cpu_devices(fault, tmp_path):
    """The four-chip cell on four forced CPU devices, in a child process
    (the device count is fixed when JAX starts): the root combine's psum
    left out must fail the check."""
    code = f"""
import sys, time
from pathlib import Path
sys.path[:0] = [{str(CHECKOUT)!r}, {str(CHECKOUT / 'src')!r},
                {str(CHECKOUT / 'chipbench' / 'tests')!r}]
import jax
from conftest import TINY_MODEL, write_bench
from chipbench import drive, harness
import test_check as tc
bench = write_bench(Path({str(tmp_path)!r}))
fault = {fault!r}
if fault == "exchange_left_out":
    jax.lax.psum = lambda x, axis_name, **kw: x
elif fault == "state_unchanged":
    build = drive.build_engine
    def build_faulty(config, params, telemetry):
        eng = build(config, params, telemetry)
        tc.FAULTS[fault](eng)
        return eng
    drive.build_engine = build_faulty
cell = harness.resolve("tier4.backlog", bench)
cell.config["model"] = dict(TINY_MODEL)
out = harness.run_cell(cell, {SEED}, 1.0, False, t_process=time.perf_counter(),
                       log=lambda s: None, require_tpu=False)
print("CORRECT", out["correct"], out["check"])
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [x for x in p.stdout.splitlines() if x.startswith("CORRECT")][-1]
    assert line.startswith("CORRECT True" if fault == "sound"
                           else "CORRECT False"), line


def test_reference_passes_itself_and_fails_a_wrong_release():
    cell = harness.resolve("papaya.backlog")
    config = dict(cell.config, model={"shapes": {"w": [64, 32], "b": [32]}})
    params = drive.make_params(config, SEED)
    entries = drive.pool_entries(drive.make_pool(params, cell.mix, SEED), 1)
    w, n = session_weights(cell.mix, 10, SEED, 0)
    rng = jax.random.PRNGKey(3)
    kw = dict(clip_norm=1.0, noise_multiplier=1.0, rng=rng, field_bits=32,
              contributors=10, value_range=4.0)
    ref = reference.release(params, tuple(entries), jnp.asarray(w, jnp.float32),
                            jnp.float32(w.sum()), jnp.float32(1.0),
                            jnp.float32(1.0), rng)[0]
    limit = cell.config["check"]["err_over_bound"]
    assert reference.compare(ref, params, entries, w, n, w.sum(), **kw) == 0
    wrong = jax.tree.map(lambda x: x.at[(3,) * x.ndim].add(1e-5), ref)
    assert reference.compare(wrong, params, entries, w, n, w.sum(), **kw) \
        > limit
    # a release without the central noise is far off too
    other = reference.release(params, tuple(entries),
                              jnp.asarray(w, jnp.float32),
                              jnp.float32(w.sum()), jnp.float32(1.0),
                              jnp.float32(0.0), rng)[0]
    assert reference.compare(other, params, entries, w, n, w.sum(), **kw) \
        > limit


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_in_bfloat16_fails(workload, bench_path):
    """The control (the reference with bf16 contributions in the program's
    place) reads above the limit at the test size."""
    cell = harness.resolve(workload, bench_path)
    config = dict(cell.config, model={"shapes": {"w": [256, 128],
                                                 "b": [128]}})
    fl = cell.config["fl"]
    buffer = harness.contributors(cell.config)
    params = drive.make_params(config, SEED)
    entries = drive.pool_entries(drive.make_pool(params, cell.mix, SEED),
                                 drive.stack_rows(cell.mix))
    w, n = session_weights(cell.mix, buffer, SEED, 1)
    x = reference.compare(None, params, entries, w, n, float(w.sum()),
                          clip_norm=fl["clip_norm"],
                          noise_multiplier=fl["noise_multiplier"],
                          rng=jax.random.PRNGKey(5),
                          field_bits=fl["secure_agg_bits"],
                          contributors=buffer, value_range=4.0,
                          dtype=jnp.bfloat16)
    assert x > cell.config["check"]["err_over_bound"]
