"""Sharding-rule unit tests (pure spec logic, no multi-device needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.launch import analysis


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def _specs_for(arch, fsdp_axis=None, mesh_shape=None):
    from repro.launch.sharding import param_specs
    cfg = registry.get_config(arch, reduced=True)
    from repro.models.model import build_model
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    mesh = FakeMesh(mesh_shape or {"data": 16, "model": 16})
    return params, param_specs(params, mesh, fsdp_axis=fsdp_axis), mesh


@pytest.mark.parametrize("arch", list(registry.ARCH_IDS))
def test_specs_divisible_and_unique(arch):
    """Every sharded dim divides its axis; no axis used twice per tensor."""
    params, specs, mesh = _specs_for(arch, fsdp_axis="data")

    def check(leaf, spec):
        assert len(spec) <= leaf.ndim
        seen = []
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % n == 0, (arch, leaf.shape, spec)
            seen.extend(axes)
        assert len(seen) == len(set(seen)), (arch, spec)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P))


def test_big_matrices_are_sharded():
    """The big 2-D weights must actually get a model-axis shard."""
    params, specs, _ = _specs_for("deepseek-7b")
    flat = jax.tree_util.tree_leaves_with_path(specs,
                                               is_leaf=lambda x: isinstance(x, P))
    sharded = {jax.tree_util.keystr(k): v for k, v in flat}
    assert any("model" in str(v) for v in sharded.values())
    # embedding vocab sharded
    emb = [v for k, v in sharded.items() if "embed" in k][0]
    assert "model" in str(emb)


def test_kv_heads_not_divisible_stay_replicated():
    """qwen2 kv=2 on model=16: wk/wv head dim must NOT be sharded."""
    params, specs, _ = _specs_for("qwen2-1.5b")
    flat = jax.tree_util.tree_leaves_with_path(specs,
                                               is_leaf=lambda x: isinstance(x, P))
    for k, v in flat:
        ks = jax.tree_util.keystr(k)
        if ks.endswith("['wk']") or ks.endswith("['wv']"):
            assert all(ax is None for ax in tuple(v)[1:]), (ks, v)


# --- HLO collective parsing ----------------------------------------------------
def test_parse_collectives_from_hlo_text():
    hlo = """
  %ar = f32[1024,256]{1,0} all-reduce(f32[1024,256]{1,0} %x), replica_groups={}
  %ag.1 = bf16[512]{0} all-gather(bf16[32]{0} %y), dimensions={0}
  %rs = (f32[128]{0}, f32[128]{0}) reduce-scatter(f32[2048]{0} %z), dimensions={0}
  %cp = u32[16]{0} collective-permute(u32[16]{0} %w), source_target_pairs={{0,1}}
  %nothing = f32[4]{0} add(f32[4]{0} %a, f32[4]{0} %b)
"""
    s = analysis.collective_summary(hlo)
    assert s["count"] == 4
    assert s["ops"]["all-reduce"]["bytes"] == 1024 * 256 * 4
    assert s["ops"]["all-reduce"]["wire_bytes"] == 2 * 1024 * 256 * 4
    assert s["ops"]["all-gather"]["bytes"] == 512 * 2
    assert s["ops"]["reduce-scatter"]["bytes"] == 2 * 128 * 4
    assert s["ops"]["collective-permute"]["bytes"] == 16 * 4


def test_roofline_terms_math():
    class FakeCompiled:
        def cost_analysis(self):
            return {"flops": 197e12, "bytes accessed": 819e9}

    hlo = "%ar = f32[125000000]{0} all-reduce(f32[125000000]{0} %x)"
    r = analysis.roofline(FakeCompiled(), hlo, model_flops=197e12 * 2, chips=2)
    assert r["t_compute_s"] == pytest.approx(1.0)
    assert r["t_memory_s"] == pytest.approx(1.0)
    assert r["t_collective_s"] == pytest.approx(2 * 5e8 / 50e9)
    assert r["dominant"] in ("compute", "memory")
    assert r["useful_flops_ratio"] == pytest.approx(1.0)


def test_peaks_are_keyed_by_device_kind():
    """The v5e's published peaks; a kind missing from the table raises."""
    p = analysis.peaks("TPU v5 lite")
    assert (p["flops_bf16"], p["hbm_bytes_per_s"]) == (197e12, 819e9)
    with pytest.raises(ValueError, match="no published peaks"):
        analysis.peaks("cpu")
