"""Structured/sketched upload compression inside the masked field.

The device->server uplink is the paper's production bottleneck, and PR 7
only optimized the *encoding* of that wire (packed sub-32-bit residues) —
not the *information* sent.  Following McMahan et al. (arXiv 1602.05629),
this module compresses the client update BEFORE it enters the secure-agg
field, so quantization, masking, dropout recovery, bit-packing and the
tier's destination-sharded ingest all run over the shorter vector:

  ``subsample``  seeded random-mask subsampling: keep ``m = ceil(rate * D)``
                 coordinates of the chunk, chosen by ranking PRF words.
  ``sketch``     structured random rotation sketch: random sign-flip
                 diagonal ∘ block-diagonal fast Walsh–Hadamard transform
                 (orthonormal, 512-wide blocks) ∘ the same PRF subsample.
                 The rotation spreads each coordinate's energy across the
                 block, so a sparse/adversarial update survives subsampling
                 (the classic randomized-Hadamard trick).

Nothing about the operators travels on the wire.  Both are regenerated
deterministically at the two ends of the push split from the engine's
session key: per chunk, ``op_key = fold_in(chunk_session_key,
COMPRESSION_TAG)`` seeds two counter-PRF stream families
(:data:`~repro.kernels.prf.TAG_SIGN` for the diagonal,
:data:`~repro.kernels.prf.TAG_SELECT` for the coordinate ranking), exactly
like the pairwise masks themselves.  When the session rolls, the operators
roll with it — a retried contribution re-encoded against the new session
(see ``faults.FaultInjector``) automatically re-derives them.

The operator is deliberately SLOT-INVARIANT within a session: the server
only ever sees the masked *sum* of client updates, and a sum commutes with
one shared linear operator — accumulating in the sketch domain and
expanding once at decode is only possible because every contributor applied
the same ``R``.  (Per-slot operators would force per-contribution
expansion, resurrecting the full-width buffers this module exists to
remove.)  Privacy is unaffected: the pairwise masks are still per-slot and
still drown the compressed coordinates in uniform field noise.

Unbiasedness: with ``S`` the uniform ``m``-of-``P`` selection and ``R`` the
orthonormal rotation, the decoder applies ``(P/m) * Rᵀ Sᵀ`` to the
aggregate; ``E[Sᵀ S] = (m/P) I`` over the PRF seed, so
``E[expand(compress(x))] = x`` (property-tested in
tests/test_compression.py).

``CompressionSpec`` is a registered-static frozen dataclass so it can hang
off ``AggregationSpec`` / ``ClientPush`` and cross jit boundaries as
compile-time metadata.  Rate 1.0 (or mode "none") canonicalizes to the
identity spec, which every consumer treats as the exact legacy code path —
the rate-1.0 == uncompressed bit-parity contract is structural, not
numerical.

This module depends only on ``jax`` and the counter PRF — never on the
aggregation layer — so kernels and protocol code can both import it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import prf

__all__ = [
    "COMPRESSION_TAG", "SKETCH_BLOCK", "CompressionSpec", "WireChunk",
    "ChunkOps", "wire_chunks", "chunk_operators", "fwht", "block_rotate",
    "block_rotate_t", "compress", "expand",
]

# fold-in tag deriving a chunk's operator key from its session key.
# Tag namespace (see aggregation.py): 0x5E55 sync, 0x7EE tee, 0xDEE tee
# noise, 0xA5 push base, 0x5A5E session seed, 0x1EAF leaf, 0x4007 root,
# 0x6B52 graph perm, 0xC401 chunk session, 0xCB01 compression operator.
COMPRESSION_TAG = 0xCB01

# Hadamard block width.  Matches the 512-element kernel/chunk block
# (aggregation.DEFAULT_CHUNK_BLOCK) so sketch-domain buffers stay aligned
# with the packed-wire layout.
SKETCH_BLOCK = 512

_MODES = ("none", "subsample", "sketch")


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class CompressionSpec:
    """Static per-session upload-compression policy.

    ``mode="none"`` or ``rate >= 1.0`` canonicalize to the identity spec
    ``CompressionSpec()``, so equality against the default spec is the
    "compression off" test and rate-1.0 follows the legacy byte-for-byte
    code path.
    """

    mode: str = "none"
    rate: float = 1.0

    def __post_init__(self):
        if self.mode not in _MODES:
            raise ValueError(
                f"compress_mode {self.mode!r}: want one of {_MODES}")
        if not 0.0 < self.rate <= 1.0:
            raise ValueError(
                f"compress_rate {self.rate} must be in (0, 1] — it is the "
                "kept fraction of each chunk's coordinates")
        if self.mode == "none" or self.rate >= 1.0:
            object.__setattr__(self, "mode", "none")
            object.__setattr__(self, "rate", 1.0)

    @property
    def identity(self) -> bool:
        return self.mode == "none"

    def describe(self) -> str:
        return ("identity" if self.identity
                else f"{self.mode}@rate={self.rate:g}")


class WireChunk(NamedTuple):
    """Wire-domain widths of one plan chunk under a compression spec.

    size    coordinates actually carried per contribution (m)
    padded  buffer/pack width the engines allocate for the chunk
    full    operator domain width P (sketch: logical size padded to the
            Hadamard block; subsample/identity: the logical size itself)
    """

    size: int
    padded: int
    full: int


class ChunkOps(NamedTuple):
    """One chunk's realized compression operator (PRF-derived).

    ``signs``/``idx`` may be traced arrays (derivation happens inside the
    engines' jitted closures, keyed by the live session key); ``mode`` /
    ``full`` / ``m`` are static.
    """

    mode: str
    full: int
    m: int
    idx: jnp.ndarray  # (m,) sorted selected coordinates in [0, full)
    signs: Optional[jnp.ndarray] = None  # (full,) ±1 f32, sketch only
    # (2,) uint32 op-key words — the fused Pallas lane regenerates the
    # TAG_SIGN stream in-kernel from these instead of loading ``signs``
    key_words: Optional[jnp.ndarray] = None


def _ceil_block(n: int) -> int:
    return -(-n // SKETCH_BLOCK) * SKETCH_BLOCK


def compressed_size(cspec: CompressionSpec, size: int) -> int:
    """m: wire coordinates for a logical chunk of ``size`` elements."""
    if cspec.identity:
        return size
    return max(1, math.ceil(cspec.rate * size))


def wire_chunks(cspec: CompressionSpec, chunks: Sequence) -> Tuple[
        WireChunk, ...]:
    """Per-chunk wire widths for a plan's chunks (objects with
    ``.size``/``.padded``).  Identity returns the plan's own widths
    verbatim — the legacy layout, untouched."""
    out = []
    for ck in chunks:
        if cspec.identity:
            out.append(WireChunk(ck.size, ck.padded, ck.size))
            continue
        full = _ceil_block(ck.size) if cspec.mode == "sketch" else ck.size
        m = compressed_size(cspec, ck.size)
        # follow the plan's own padding rule: flat single-chunk layouts are
        # exact-width, kernel-blocked layouts pad to the 512 block
        padded = m if ck.padded == ck.size else _ceil_block(m)
        out.append(WireChunk(m, padded, full))
    return tuple(out)


def smallest_m(ranks: jnp.ndarray, m: int) -> jnp.ndarray:
    """Ascending positions of the ``m`` smallest int32 ``ranks``, ties
    taken in position order — ``sort(argsort(ranks)[:m])`` without sorting.

    A bisection over the 32-bit order finds the m-th smallest rank ``t``
    (32 counting passes); every rank below ``t`` is kept, plus the first
    ties at ``t`` up to ``m``.  Sorting a model-width chunk costs the TPU
    compiler over a minute; this costs it a few passes.
    """
    # flip the sign bit: signed int32 order becomes unsigned order.  The
    # barrier materializes the ranks once: fused into the 32 counting
    # passes and the cumsum, the PRF that makes them takes the TPU
    # compiler minutes.
    u = jax.lax.optimization_barrier(jax.lax.bitcast_convert_type(
        ranks.astype(jnp.int32), jnp.uint32) ^ jnp.uint32(1 << 31))

    def bisect(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        enough = jnp.sum((u <= mid).astype(jnp.int32)) >= m
        return jnp.where(enough, lo, mid + 1), jnp.where(enough, mid, hi)

    t, _ = jax.lax.fori_loop(0, 32, bisect,
                             (jnp.uint32(0), jnp.uint32(0xFFFFFFFF)))
    below, ties = u < t, u == t
    room = m - jnp.sum(below.astype(jnp.int32))
    keep = below | (ties & (jnp.cumsum(ties.astype(jnp.int32)) <= room))
    return jnp.nonzero(keep, size=m)[0].astype(jnp.int32)


def chunk_operators(op_key, mode: str, size: int, rate: float) -> ChunkOps:
    """Realize one chunk's operator from its fold-in key.

    Both ends of the push split call this with the SAME ``op_key``
    (``fold_in(chunk_session_key, COMPRESSION_TAG)``), so no index or seed
    payload ever crosses the wire.  Selection ranks ``TAG_SELECT`` PRF
    words (a seeded uniform ``m``-of-``full`` subset); the sketch adds a
    ``TAG_SIGN`` ±1 diagonal.
    """
    full = _ceil_block(size) if mode == "sketch" else size
    m = max(1, math.ceil(rate * size))
    ow0, ow1 = prf.key_words(op_key)
    ranks = prf.stream_block(ow0, ow1, full, tag=prf.TAG_SELECT)
    idx = smallest_m(ranks, m)
    signs = None
    if mode == "sketch":
        bits = prf.stream_block(ow0, ow1, full, tag=prf.TAG_SIGN)
        signs = 1.0 - 2.0 * (bits & 1).astype(jnp.float32)
    return ChunkOps(mode=mode, full=full, m=m, idx=idx, signs=signs,
                    key_words=jnp.stack((ow0, ow1)))


def fwht(x: jnp.ndarray) -> jnp.ndarray:
    """Orthonormal fast Walsh–Hadamard transform over the last axis.

    The classic in-place butterfly: at stage ``h`` element ``i`` meets its
    partner ``i ^ h`` and keeps ``x_i + x_p`` when bit ``h`` of ``i`` is
    clear, ``x_p - x_i`` when it is set — the ``(a+b, a-b)`` pair of the
    butterfly.  The partner comes from a rotation of the axis by ``h``
    either way, so no stage ever views the axis with a short minor
    dimension (which a TPU would pad out to full lanes).  One final
    ``1/sqrt(n)`` makes it orthonormal (and therefore self-inverse).  The
    kernel body and the ref.py oracle do the same adds in the same order,
    so host, kernel and oracle agree bit-for-bit.
    """
    n = x.shape[-1]
    if n & (n - 1):
        raise ValueError(f"fwht length {n} must be a power of two")
    lane = jnp.arange(n, dtype=jnp.int32)
    h = 1
    while h < n:
        lo = (lane & h) == 0
        partner = jnp.where(lo, jnp.roll(x, -h, axis=-1),
                            jnp.roll(x, h, axis=-1))
        x = jnp.where(lo, x + partner, partner - x)
        h *= 2
    return x * jnp.float32(1.0 / math.sqrt(n))


def _blocked(fn, x: jnp.ndarray) -> jnp.ndarray:
    lead, P = x.shape[:-1], x.shape[-1]
    y = fn(x.reshape(lead + (P // SKETCH_BLOCK, SKETCH_BLOCK)))
    return y.reshape(lead + (P,))


def block_rotate(x: jnp.ndarray, signs: jnp.ndarray) -> jnp.ndarray:
    """The rotation R = blockFWHT ∘ diag(signs): y = H (s ⊙ x)."""
    return _blocked(fwht, x * signs)


def block_rotate_t(y: jnp.ndarray, signs: jnp.ndarray) -> jnp.ndarray:
    """Rᵀ = R⁻¹ (H symmetric orthonormal): x = s ⊙ H y."""
    return _blocked(fwht, y) * signs


def compress(x: jnp.ndarray, ops: ChunkOps) -> jnp.ndarray:
    """(…, size) chunk values -> (…, m) sketch-domain coordinates."""
    if ops.mode == "none":
        return x
    pad = ops.full - x.shape[-1]
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    if ops.mode == "sketch":
        x = block_rotate(x, ops.signs)
    return jnp.take(x, ops.idx, axis=-1)


def expand(z: jnp.ndarray, ops: ChunkOps, size: int) -> jnp.ndarray:
    """(…, m) sketch-domain AGGREGATE -> unbiased (…, size) estimate.

    Applies ``(full/m) · Rᵀ Sᵀ``: scatter the kept coordinates back,
    un-rotate, slice off the Hadamard pad.  Runs once per decode, over the
    already-summed aggregate — never per contribution.
    """
    if ops.mode == "none":
        return z
    z = z * jnp.float32(ops.full / ops.m)
    full = jnp.zeros(z.shape[:-1] + (ops.full,), z.dtype)
    full = full.at[..., ops.idx].set(z)
    if ops.mode == "sketch":
        full = block_rotate_t(full, ops.signs)
    return full[..., :size]
