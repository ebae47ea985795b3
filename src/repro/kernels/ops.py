"""Jitted public wrappers for the Pallas kernels.

On a TPU runtime the kernels compile natively; on this CPU container they run
in ``interpret=True`` mode (the kernel body executed by the Pallas
interpreter), which is what the test suite validates against the pure-jnp
oracles in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import bitagg as _bitagg
from repro.kernels import dp_clip as _dp_clip
from repro.kernels import flash_decode as _flash
from repro.kernels import ref as ref  # noqa: F401 (re-exported for callers)
from repro.kernels import secure_agg as _sa


def on_tpu() -> bool:
    """The one backend decision of the repo: on a TPU the Pallas kernels
    compile natively; anywhere else (the CPU test suite) they run in
    interpret mode."""
    return jax.default_backend() == "tpu"


def _interp() -> bool:
    return not on_tpu()


@functools.partial(jax.jit, static_argnames=("clip_norm",))
def dp_clip_reduce(deltas: jnp.ndarray, clip_norm: float) -> jnp.ndarray:
    """(C, D) client deltas -> (D,) sum of per-client-clipped deltas."""
    return _dp_clip.dp_clip_reduce(deltas, clip_norm, interpret=_interp())


@functools.partial(jax.jit)
def client_sq_norms(deltas: jnp.ndarray) -> jnp.ndarray:
    return _dp_clip.sq_norms(deltas, interpret=_interp())


@functools.partial(jax.jit, static_argnames=("scale", "value_range"))
def secure_agg_encode(x, mask, uniforms, scale: float, value_range: float):
    return _sa.quantize_mask(x, mask, uniforms, scale, value_range,
                             interpret=_interp())


@functools.partial(jax.jit, static_argnames=("scale",))
def secure_agg_decode(q, scale: float):
    return _sa.dequantize(q, scale, interpret=_interp())


@functools.partial(jax.jit, static_argnames=("flip_prob",))
def fa_bit_counts(values, thresholds, uniforms, flip_prob: float):
    return _bitagg.bit_counts(values, thresholds, uniforms, flip_prob,
                              interpret=_interp())


@functools.partial(jax.jit, static_argnames=("window",))
def flash_decode_attention(q, k, v, slot_pos, pos, window: int = 0):
    return _flash.flash_decode(q, k, v, slot_pos, pos, window=window,
                               interpret=_interp())
