"""Counter-based pairwise-mask PRF — the shared core of host and kernel paths.

Secure-aggregation pairwise masks are streams of uniform int32 words keyed by
``(session_key, lo_slot, hi_slot)`` and indexed by flat element position.  A
*counter-based* PRF makes the stream random-access: any tile of any mask can
be generated wherever it is consumed — inside a Pallas kernel on a VMEM tile
just as well as on the host — so masks never need to be materialized in HBM
and never travel between host and device.

The permutation is Threefry-2x32 (Salmon et al., SC'11) at 13 rounds — the
documented Crush-resistant round count for the 2x32 variant; ``rounds=20``
reproduces the full-strength schedule bit-for-bit (test-verified against
JAX's own threefry_2x32).  Everything here is plain ``jnp`` on uint32, so the
same functions trace into XLA host code AND into Pallas kernel bodies.

Stream layout (the oracle contract, shared by kernels/ref.py and the Pallas
kernels in kernels/secure_agg.py):

  pair key   (pk0, pk1) = threefry(session_key, (lo, hi))
  element e  word       = threefry(pair_key,    (e >> 1, tag))[e & 1]

Two consecutive elements share one Threefry counter (each evaluation
yields two 32-bit lanes, and the element's parity picks one); the ``tag``
word separates independent stream families drawn from one key (masks vs
stochastic-rounding uniforms).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

U32 = jnp.uint32

# Default round count: Threefry-2x32-13, the minimum listed as passing
# BigCrush in Salmon et al. (2011), Table 2.  20 = the full-strength default.
DEFAULT_ROUNDS = 13

_ROT = (13, 15, 26, 6, 17, 29, 16, 24)
_PARITY = 0x1BD11BDA  # Threefry key-schedule parity constant (2x32)

# counter tags: disjoint stream families under one pair/key (see layout note)
TAG_MASK = 0
TAG_UNIFORM = 1
TAG_SIGN = 2  # compression: random sign-flip diagonal (rotation sketch)
TAG_SELECT = 3  # compression: coordinate-selection ranking words


def key_words(key) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(k0, k1) uint32 words of a JAX PRNGKey (old- or new-style)."""
    data = jax.random.key_data(key).astype(U32).reshape(-1)
    return data[0], data[1]


def _rotl(x, r: int):
    return (x << U32(r)) | (x >> U32(32 - r))


def threefry2x32(k0, k1, x0, x1, *, rounds: int = DEFAULT_ROUNDS):
    """The Threefry-2x32 block cipher on uint32 arrays (broadcasting).

    Returns the two output lanes.  ``rounds=20`` is bit-identical to JAX's
    internal ``threefry_2x32`` (same rotation and key-injection schedule);
    lower round counts truncate the schedule exactly as Random123 does
    (injections after every 4th round only).
    """
    k0 = jnp.asarray(k0).astype(U32)
    k1 = jnp.asarray(k1).astype(U32)
    x0 = jnp.asarray(x0).astype(U32)
    x1 = jnp.asarray(x1).astype(U32)
    ks = (k0, k1, k0 ^ k1 ^ U32(_PARITY))
    x0 = x0 + ks[0]
    x1 = x1 + ks[1]
    for i in range(rounds):
        x0 = x0 + x1
        x1 = _rotl(x1, _ROT[i % 8]) ^ x0
        if (i + 1) % 4 == 0:
            j = (i + 1) // 4
            x0 = x0 + ks[j % 3]
            x1 = x1 + ks[(j + 1) % 3] + U32(j)
    return x0, x1


def pair_keys(k0, k1, lo, hi, *, rounds: int = DEFAULT_ROUNDS):
    """Per-pair stream keys: one Threefry of the (lo, hi) slot ids."""
    return threefry2x32(k0, k1, lo, hi, rounds=rounds)


def stream_at(pk0, pk1, e, *, tag: int = TAG_MASK,
              rounds: int = DEFAULT_ROUNDS) -> jnp.ndarray:
    """PRF words at arbitrary element positions ``e`` (int array) -> int32.

    The tile/random-access form used INSIDE kernels: every element computes
    its own word from its flat position, so any tiling of the stream agrees
    bit-for-bit with the host path (``stream_block``).  Adjacent elements
    share a counter and select lanes by parity.
    """
    e = jnp.asarray(e).astype(U32)
    y0, y1 = threefry2x32(pk0, pk1, e >> U32(1), jnp.full_like(e, U32(tag)),
                          rounds=rounds)
    return jnp.where((e & U32(1)) == 0, y0, y1).astype(jnp.int32)


def stream_block(pk0, pk1, length: int, *, tag: int = TAG_MASK,
                 offset: int = 0,
                 rounds: int = DEFAULT_ROUNDS) -> jnp.ndarray:
    """The host path: ``stream_at(offset + arange(length))``.

    ``pk0/pk1`` may carry leading batch dims; the stream axis is appended
    last.  ``offset`` shifts the element positions, so a chunk of a longer
    stream (a ``ParamPlan`` chunk's slice of the model-wide uniform stream)
    is bit-identical to the corresponding slice of the full block.

    Every element evaluates its own counter and keeps the lane its parity
    selects.  Interleaving the two lanes of one evaluation instead would
    halve the Threefry work, but the (n, 2) pairs it stacks are laid out
    on a TPU as lane-padded tiles 64x their size — at model widths that
    alone exceeds the chip's memory.
    """
    pk0 = jnp.asarray(pk0).astype(U32)[..., None]
    pk1 = jnp.asarray(pk1).astype(U32)[..., None]
    e = U32(offset) + jnp.arange(length, dtype=U32)
    return stream_at(pk0, pk1, e, tag=tag, rounds=rounds)


def uniform_block(uk0, uk1, length: int, *, offset: int = 0,
                  rounds: int = DEFAULT_ROUNDS) -> jnp.ndarray:
    """f32 uniforms in [0, 1) from the TAG_UNIFORM stream family.

    Top 24 bits of each word scaled by 2^-24 — the standard exact-f32
    construction; bit-identical between host and in-kernel generation.
    """
    bits = stream_block(uk0, uk1, length, tag=TAG_UNIFORM, offset=offset,
                        rounds=rounds)
    return bits_to_uniform(bits)


def bits_to_uniform(bits: jnp.ndarray) -> jnp.ndarray:
    """int32 PRF words -> f32 uniforms in [0, 1) (top 24 bits, exact).

    The 24-bit value goes through int32 on its way to f32: it is exact in
    both, and Mosaic has no uint32 -> f32 conversion.
    """
    top = (bits.astype(U32) >> U32(8)).astype(jnp.int32)
    return top.astype(jnp.float32) * jnp.float32(2.0 ** -24)
