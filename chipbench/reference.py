"""The plain float32 reference of one release, and the comparison with it.

A release of the aggregation service (FedBuff in the TEE, a SecAgg+ round, or
the two-level tier) promises, per parameter element:

    new = p + (mean + noise),   mean = sum_i w_i c_i x_i / W,   W = sum_i w_i

over the contributions i folded into it: x_i the client delta, w_i its
staleness weight ``(1 + s_i) ** -0.5`` (1 for synchronous rounds), c_i its
clip scale ``min(1, C / ||x_i||)`` and ``noise`` the central DP draw inside
the TEE, ``z C / W`` times a standard normal per leaf, keyed
``split(fold_in(rng, 0xDEE), leaves)`` from the release's ``rng`` (FedAvg
with server learning rate 1).  Secure aggregation (masks, their cancellation
and the recovery of absent slots) and the fixed-point field are exact by
design, so the reference leaves them out: what they may cost is the
fixed-point rounding below, nothing more.

The reference imports nothing of the program.  Contributions arrive as
weights per entry of the harness's delta pool (arrival i uses entry
i mod pool), so ``a_j`` sums the weights of every arrival of entry j and the
reference computes ``sum_j a_j c_j x_j`` once per entry.

Error bound per element (unit roundoff u = 2^-24, n contributions, scale s of
the fixed-point field, S = sum_j a_j c_j |x_j|):

  program: each contribution is scaled (two f32 roundings: <= 2u|c x w|),
    then stochastically rounded to an integer (< 1/s each); the integer sum
    is exact; its f32 conversion, /s and /W round three times (3u).  So
    |mean_prog - mean| < n / (s W) + 5u S / W.
  reference: the weight and clip products and the sum over <= n entries:
    <= (n + 2) u S / W; the clip norms of both sides, reduced in different
    orders over d elements, differ by at most ~log2(d) u each.
  noise: std = zC/W, with W summed in another order on each side:
    <= (ceil(log2 n) + 3) u |noise|.
  y = mean + noise: each side rounds once, by half the spacing of f32 at
    magnitude <= S/W + |noise| + the bounds above.
  params: both sides round p + y once each: spacing(M) at magnitude
    M = |ref| + the bound on y.

  bound_y = 1.01 (n / (s W) + (2n + 7 + 2 log2 d) u S / W)
            + (log2 n + 3) u |noise|
            + spacing(S / W + |noise| + that)
  bound = bound_y + spacing(|ref| + bound_y)

The number compared is ``err_over_bound``: the largest |got - ref| / bound
over every element of the sampled releases.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

U = 2.0 ** -24  # float32 unit roundoff
NOISE_TAG = 0xDEE  # the release rng's fold for the central noise stream


def fixed_point_scale(bits: int, contributors: int, value_range: float
                      ) -> float:
    """Scale of the secure-aggregation field: a full aggregate of
    ``contributors`` rows (each with its rounding carry) cannot wrap."""
    levels = (2 ** (bits - 1) - 1) / contributors - 1.0
    return max(levels, 1.0) / value_range


def _norm(tree, dtype):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(dtype)))
                        for x in jax.tree.leaves(tree)))


@partial(jax.jit, static_argnames=("dtype",))
def release(params, pool, a, total_weight, clip_norm, noise_multiplier, rng,
            dtype=jnp.float32):
    """The reference release.

    params: pytree before the release; pool: tuple of delta pytrees (the
    harness's pool entries); a: (len(pool),) f32 summed staleness weight of
    the arrivals of each entry; total_weight: W.  ``dtype`` is the precision
    of the contributions and their sum (float32 for the reference, lower for
    the control).  Returns (new params, S, noise) as pytrees of f32.
    """
    leaves, treedef = jax.tree.flatten(params)
    acc = [jnp.zeros(x.shape, dtype) for x in leaves]
    absum = [jnp.zeros(x.shape, jnp.float32) for x in leaves]
    for j, entry in enumerate(pool):
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(
            _norm(entry, jnp.float32), 1e-12))
        coef = (a[j] * scale).astype(dtype)
        for i, x in enumerate(jax.tree.leaves(entry)):
            cx = x.astype(dtype) * coef
            acc[i] = acc[i] + cx
            absum[i] = absum[i] + jnp.abs(cx.astype(jnp.float32))
    w = jnp.maximum(total_weight, 1e-9)
    std = noise_multiplier * clip_norm / w
    keys = jax.random.split(jax.random.fold_in(rng, NOISE_TAG), len(leaves))
    noise = [std * jax.random.normal(k, x.shape, jnp.float32)
             for k, x in zip(keys, leaves)]
    new = [p.astype(jnp.float32) + ((s / w.astype(dtype)).astype(jnp.float32)
                                    + e)
           for p, s, e in zip(leaves, acc, noise)]
    return tuple(jax.tree.unflatten(treedef, t)
                 for t in (new, [s / w for s in absum], noise))


@jax.jit
def err_over_bound(got, ref, abs_mean, noise, n, total_weight, scale, d_log2):
    """max over elements of |got - ref| / bound (see the module docstring).

    ``abs_mean`` is S / W, ``n`` the contributions folded, ``scale`` the
    field's fixed-point scale s, ``d_log2`` ceil(log2 d)."""
    worst = jnp.float32(0.0)
    step = n / (scale * jnp.maximum(total_weight, 1e-9))
    for g, r, am, e in zip(jax.tree.leaves(got), jax.tree.leaves(ref),
                           jax.tree.leaves(abs_mean), jax.tree.leaves(noise)):
        mean_bound = 1.01 * (step + (2 * n + 7 + 2 * d_log2) * U * am) \
            + (jnp.ceil(jnp.log2(n)) + 3) * U * jnp.abs(e)
        y_bound = mean_bound + jnp.spacing(am + jnp.abs(e) + mean_bound)
        bound = y_bound + jnp.spacing(jnp.abs(r) + y_bound)
        worst = jnp.maximum(worst, jnp.max(
            jnp.abs(g.astype(jnp.float32) - r) / bound))
    return worst


def compare(got, params_before, pool, a, n, total_weight, *, clip_norm,
            noise_multiplier, rng, field_bits, contributors, value_range,
            dtype=jnp.float32):
    """``err_over_bound`` of released params ``got`` against the reference.

    ``dtype`` below float32 computes the control (the same comparison with
    the contributions summed in that precision put in the program's
    place): it must come out far above the limit.  Returns a float.
    """
    d = sum(int(x.size) for x in jax.tree.leaves(params_before))
    r, am, e = release(params_before, tuple(pool), jnp.asarray(a, jnp.float32),
                       jnp.float32(total_weight), jnp.float32(clip_norm),
                       jnp.float32(noise_multiplier), rng, dtype=jnp.float32)
    if dtype != jnp.float32:
        got = release(params_before, tuple(pool), jnp.asarray(a, jnp.float32),
                      jnp.float32(total_weight), jnp.float32(clip_norm),
                      jnp.float32(noise_multiplier), rng, dtype=dtype)[0]
    s = fixed_point_scale(field_bits, contributors, value_range)
    return float(err_over_bound(got, r, am, e, jnp.float32(n),
                                jnp.float32(total_weight), jnp.float32(s),
                                jnp.float32(math.ceil(math.log2(d)))))
