"""Kernel micro-benchmarks: jnp-ref timing, plus the analytic roofline of
each kernel (bytes touched / HBM bandwidth of the chip it runs on).

The roofline is printed only on a TPU, from the peaks of its
``device_kind`` (``launch.analysis.PEAKS``): a CPU run has no device
roofline to report.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.kernels import ref
from repro.kernels.ops import on_tpu
from repro.launch.analysis import peaks


def _roofline(nbytes: float) -> str:
    """``tpu_roofline_us=`` of a memory-bound kernel moving ``nbytes``."""
    if not on_tpu():
        return ""
    hbm = peaks(jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    return f"tpu_roofline_us={nbytes / hbm * 1e6:.1f}"


def run() -> None:
    key = jax.random.PRNGKey(0)

    # dp_clip: C clients x D params
    C, D = 64, 1 << 20
    deltas = jax.random.normal(key, (C, D)) * 0.3
    f = jax.jit(lambda x: ref.dp_clip_reduce(x, 1.0))
    us = time_fn(f, deltas)
    bytes_touched = deltas.size * 4 * 2  # read twice (norms + reduce)
    emit("kernels/dp_clip_ref_jnp", us, _roofline(bytes_touched))

    # secure agg encode
    D2 = 1 << 22
    x = jax.random.normal(key, (D2,))
    mask = jax.random.randint(key, (D2,), -2 ** 31, 2 ** 31 - 1, jnp.int32)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (D2,))
    f = jax.jit(lambda a, m, uu: ref.quantize_mask(a, m, 1 << 20, uu, 4.0))
    us = time_fn(f, x, mask, u)
    emit("kernels/secure_agg_encode_ref_jnp", us, _roofline(D2 * 4 * 4))

    # bitagg
    N, F, T = 4096, 32, 32
    vals = jax.random.normal(key, (N, F))
    thr = jnp.linspace(-3, 3, T)
    uu = jax.random.uniform(key, (N, F, T))
    f = jax.jit(lambda v, t, u_: ref.bit_counts(v, t, u_, 0.1))
    us = time_fn(f, vals, thr, uu)
    emit("kernels/bitagg_ref_jnp", us, _roofline(N * F * T * 4))

    # flash decode vs naive decode (the memory win)
    B, H, KV, hd, W = 8, 16, 8, 128, 32768
    q = jax.random.normal(key, (B, H, hd)) * hd ** -0.5
    k = jax.random.normal(jax.random.fold_in(key, 2), (B, W, KV, hd),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 3), (B, W, KV, hd),
                          jnp.bfloat16)
    slot = jnp.arange(W)

    def naive(q, k, v):
        rep = H // KV
        qg = q.reshape(B, KV, rep, hd)
        s = jnp.einsum("bgrk,bsgk->bgrs", qg,
                       k.astype(jnp.float32))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bgrs,bsgk->bgrk", p, v.astype(jnp.float32))

    us = time_fn(jax.jit(naive), q, k, v)
    cache_bytes = 2 * B * W * KV * hd * 2
    emit("kernels/decode_attention_naive_jnp", us,
         f"cache={cache_bytes / 2**20:.0f}MiB;{_roofline(cache_bytes)}")
    emit("kernels/flash_decode_score_memory_saved", 0.0,
         f"{B * H * W * 4 * 2 / 2**20:.0f}MiB scores never materialized")


if __name__ == "__main__":
    run()
