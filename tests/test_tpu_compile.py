"""The main-path Pallas kernels compile for a TPU v5e at whisper-tiny width.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
described (not attached) ``v5e:2x2`` topology, one chip of it, at the
widths the smoke run drives (D = 39,593,856, the whole whisper-tiny
delta as one ParamPlan chunk, 8 session slots), vmapped as the engines
vmap them (over clients, rows and leaves), where each case must stay ONE
kernel launch.  This catches what
interpret mode cannot — unaligned blocks, layouts the chip refuses, VMEM
and HBM budgets — at no chip time.  The topology is described inside a
module fixture, never at import, so every test worker collects the same
tests and only the worker given this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import dp_clip as kclip
from repro.kernels import secure_agg as ksa

KERNEL = 'custom_call_target="tpu_custom_call"'
SLOTS, DEGREE, BITS = 8, 4, 19


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def width():
    """The whisper-tiny delta width, from the registry config's shapes."""
    from repro.configs import registry
    from repro.models.model import build_model

    model = build_model(registry.get_config("whisper-tiny", reduced=False))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree.leaves(shapes))


def _session(mk, nbrs=None):
    return ksa.SessionMeta(key_words=mk, num_slots=SLOTS, degree=DEGREE,
                           neighbors=nbrs)


def _cases(D):
    """name -> (function, argument shapes)."""
    f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32
    kw, slot = ((2,), u32), ((), i32)
    Dp = -(-D // 512) * 512
    nw = -(-D * BITS // 32)
    return {
        "sq_norms": (kclip.sq_norms, [((SLOTS, Dp), f32)]),
        "quantize_prf_unmasked": (
            lambda x, uk, s: ksa.quantize_mask_prf(x, 1e3, s, uk, None),
            [((D,), f32), kw, slot]),
        "quantize_mask_prf_ring": (
            lambda x, uk, mk, s: ksa.quantize_mask_prf(x, 1e3, s, uk,
                                                       _session(mk)),
            [((D,), f32), kw, kw, slot]),
        "quantize_mask_prf_random_graph": (
            lambda x, uk, mk, s, nb: ksa.quantize_mask_prf(
                x, 1e3, s, uk, _session(mk, nb)),
            [((D,), f32), kw, kw, slot, ((SLOTS, DEGREE), i32)]),
        "quantize_mask_prf_vmapped_rows": (
            jax.vmap(lambda x, uk, mk, s: ksa.quantize_mask_prf(
                x, 1e3, s, uk, _session(mk)), in_axes=(0, None, None, 0)),
            [((4, D), f32), kw, kw, ((4,), i32)]),
        "quantize_mask_prf_nested_vmap_random_graph": (
            jax.vmap(jax.vmap(
                lambda x, uk, mk, s, nb: ksa.quantize_mask_prf(
                    x, 1e3, s, uk, _session(mk, nb)),
                in_axes=(0, 0, None, 0, None)), in_axes=(0, 0, 0, 0, None)),
            [((2, 2, D), f32), ((2, 2, 2), u32), ((2, 2), u32),
             ((2, 2), i32), ((SLOTS, DEGREE), i32)]),
        "weighted_quantize_accum_session_ring": (
            lambda x, w, u, mk: ksa.weighted_quantize_accum(
                x, w, u, 1e3, session=_session(mk)),
            [((SLOTS, D), f32), ((SLOTS,), f32), ((SLOTS, D), f32), kw]),
        "weighted_quantize_accum_session_random_graph": (
            lambda x, w, u, mk, nb: ksa.weighted_quantize_accum(
                x, w, u, 1e3, session=_session(mk, nb)),
            [((SLOTS, D), f32), ((SLOTS,), f32), ((SLOTS, D), f32), kw,
             ((SLOTS, DEGREE), i32)]),
        "weighted_quantize_accum_session_vmapped_leaves": (
            jax.vmap(lambda x, w, u, mk, off: ksa.weighted_quantize_accum(
                x, w, u, 1e3, session=ksa.SessionMeta(
                    key_words=mk, num_slots=SLOTS, degree=DEGREE,
                    slot_offset=off))),
            [((2, SLOTS // 2, D), f32), ((2, SLOTS // 2), f32),
             ((2, SLOTS // 2, D), f32), ((2, 2), u32), ((2,), i32)]),
        "pack_residues_19bit": (
            functools.partial(ksa.pack_residues, bits=BITS), [((D,), i32)]),
        "unpack_residues_19bit": (
            functools.partial(ksa.unpack_residues, size=D, bits=BITS),
            [((nw,), u32)]),
        "rotate_quantize_prf": (
            lambda x, ok, uk: ksa.rotate_quantize_prf(x, 1e3, ok, uk),
            [((D,), f32), kw, kw]),
        "rotate_quantize_prf_vmapped_rows": (
            jax.vmap(lambda x, ok, uk: ksa.rotate_quantize_prf(x, 1e3, ok,
                                                               uk),
                     in_axes=(0, None, 0)),
            [((4, D), f32), kw, ((4, 2), u32)]),
        "dequantize": (functools.partial(ksa.dequantize, scale=1e3),
                       [((D,), i32)]),
    }


@pytest.mark.parametrize("name", sorted(_cases(1)))
def test_kernel_compiles_for_v5e(name, one_chip, width):
    fn, shapes = _cases(width)[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # one kernel launch per case: a vmap adds a grid axis, never a launch
    assert compiled.as_text().count(KERNEL) == 1
