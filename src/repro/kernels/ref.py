"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels import prf


# --- dp_clip ---------------------------------------------------------------
def sq_norms(deltas: jnp.ndarray) -> jnp.ndarray:
    """deltas: (C, D) -> per-client sum of squares (C,) in f32."""
    return jnp.sum(jnp.square(deltas.astype(jnp.float32)), axis=1)


def clip_scale_accumulate(deltas: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """sum_c scales[c] * deltas[c] -> (D,) f32 (the clipped-update reduce)."""
    return jnp.einsum("cd,c->d", deltas.astype(jnp.float32),
                      scales.astype(jnp.float32))


def dp_clip_reduce(deltas: jnp.ndarray, clip_norm: float) -> jnp.ndarray:
    """Fused per-client clip + accumulate: the DP-SGD hot loop."""
    nrm = jnp.sqrt(sq_norms(deltas))
    scales = jnp.minimum(1.0, clip_norm / jnp.maximum(nrm, 1e-12))
    return clip_scale_accumulate(deltas, scales)


# --- secure_agg --------------------------------------------------------------
def quantize_mask(x: jnp.ndarray, mask: jnp.ndarray, scale: float,
                  uniforms: jnp.ndarray, value_range: float = None) -> jnp.ndarray:
    """Fixed-point stochastic-round encode + additive mask (mod 2^32).

    x: (D,) f32; mask: (D,) int32; uniforms: (D,) f32 in [0,1).
    """
    xf = x.astype(jnp.float32)
    if value_range is not None:
        xf = jnp.clip(xf, -value_range, value_range)
    xf = xf * scale
    floor = jnp.floor(xf)
    bit = (uniforms < (xf - floor)).astype(jnp.float32)
    q = (floor + bit).astype(jnp.int32)
    return q + mask  # int32 wraparound


def dequantize(q: jnp.ndarray, scale: float) -> jnp.ndarray:
    return q.astype(jnp.float32) / scale


def pack_residues(q: jnp.ndarray, bits: int) -> jnp.ndarray:
    """(D,) residues -> (ceil(D*bits/32),) uint32 words, bit by bit.

    Deliberately the slow, obvious formulation: for each of the 32 bit
    lanes of each output word, find which element/bit of the dense
    little-endian stream lands there and OR it in.  Independent of both
    the host codec and the kernel (which work a 32-element group at a
    time), so agreement is three-way evidence of the layout.
    """
    (D,) = q.shape
    nwords = -(-D * bits // 32)
    v = q.astype(jnp.uint32) & jnp.uint32((1 << bits) - 1)
    out = jnp.zeros((nwords,), jnp.uint32)
    for b in range(32):
        pos = 32 * jnp.arange(nwords, dtype=jnp.int32) + b  # stream bit index
        e = pos // bits
        r = (pos % bits).astype(jnp.uint32)
        bit = jnp.where(e < D, (v[jnp.clip(e, 0, D - 1)] >> r) & 1, 0)
        out = out | (bit << b)
    return out


def unpack_residues(words: jnp.ndarray, size: int, bits: int) -> jnp.ndarray:
    """Inverse of :func:`pack_residues`, also bit by bit."""
    out = jnp.zeros((size,), jnp.uint32)
    for r in range(bits):
        pos = bits * jnp.arange(size, dtype=jnp.int32) + r  # stream bit index
        w0 = pos // 32
        b = (pos % 32).astype(jnp.uint32)
        out = out | (((words[w0] >> b) & 1) << r)
    return out.astype(jnp.int32)


def weighted_quantize_accum(x: jnp.ndarray, weights: jnp.ndarray,
                            uniforms: jnp.ndarray, scale: float,
                            masks: jnp.ndarray = None) -> jnp.ndarray:
    """out[d] = sum_c [quantize(weights[c] * x[c, d]) + masks[c, d]] mod 2^32.

    x, uniforms: (C, D); weights: (C,); masks: optional (C, D) int32 pairwise
    session masks (cancel over a full session).  The buffered-async
    aggregation loop.
    """
    xf = x.astype(jnp.float32) * weights.astype(jnp.float32)[:, None] * scale
    floor = jnp.floor(xf)
    bit = (uniforms < (xf - floor)).astype(jnp.float32)
    q = (floor + bit).astype(jnp.int32)
    if masks is not None:
        q = q + masks  # int32 add wraps mod 2^32
    return q.sum(0)  # int32 add wraps mod 2^32


# --- in-kernel PRF mask lanes -------------------------------------------------
# Oracles for the counter-based pairwise-PRF paths.  Deliberately assembled
# the "slow, obvious" way — a Python loop over the other slots, one
# ``prf.stream_at`` word lookup per pair at explicit element positions — so
# the kernels' tiled/offset generation AND the batched host generation in
# core/fl/secure_agg.py are both checked against the same spec:
#   word(session_key, lo, hi, e) = threefry(pair_key(lo, hi), (e>>1, tag))[e&1]

def mask_graph_neighbors(slot: int, num_slots: int, degree: int = 0,
                         perm=None):
    """The slots ``slot`` shares a pairwise mask with (static Python form).

    degree 0 = complete graph; even k = ring ((slot +- j) % num_slots,
    j = 1..k/2) — the SecAgg+-style sparse session graph.  ``perm`` (a
    host-readable permutation of range(num_slots)) relabels the ring into
    the random k-regular session graph: the neighbours of ``slot`` become
    ``perm[(perm^-1[slot] +- j) % num_slots]``.
    """
    if degree <= 0 or degree >= num_slots - 1:
        return [d for d in range(num_slots) if d != slot]
    assert degree % 2 == 0, degree
    if perm is None:
        pos, vert = slot, list(range(num_slots))
    else:
        vert = [int(v) for v in perm]
        pos = vert.index(slot)
    return [vert[(pos + j) % num_slots] for j in range(1, degree // 2 + 1)] \
        + [vert[(pos - j) % num_slots] for j in range(1, degree // 2 + 1)]


def prf_session_mask(D: int, slot: int, num_slots: int, mask_key_words,
                     degree: int = 0, perm=None) -> jnp.ndarray:
    """The pairwise session mask of ``slot``, one pair stream at a time."""
    k0, k1 = jnp.asarray(mask_key_words, prf.U32)
    e = jnp.arange(D)
    total = jnp.zeros((D,), jnp.int32)
    for d in mask_graph_neighbors(slot, num_slots, degree, perm):
        lo, hi = min(slot, d), max(slot, d)
        pk0, pk1 = prf.pair_keys(k0, k1, jnp.uint32(lo), jnp.uint32(hi))
        m = prf.stream_at(pk0, pk1, e)
        total = total + (m if slot == lo else -m)  # wraps mod 2^32
    return total


def prf_uniforms(D: int, uniform_key_words, offset: int = 0) -> jnp.ndarray:
    """Stochastic-rounding uniforms of the fused push path, per position.

    ``offset`` shifts the element positions (a ParamPlan chunk's slice of
    the model-wide TAG_UNIFORM stream).
    """
    u0, u1 = jnp.asarray(uniform_key_words, prf.U32)
    return prf.bits_to_uniform(
        prf.stream_at(u0, u1, offset + jnp.arange(D), tag=prf.TAG_UNIFORM))


def quantize_mask_prf(x: jnp.ndarray, scale: float, slot: int,
                      uniform_key_words, session, perm=None,
                      u_offset: int = 0) -> jnp.ndarray:
    """Oracle for the fused masked-push kernel: q(x * scale) + mask[slot].

    ``session`` is the kernels' session-meta lane (anything with
    ``key_words`` / ``num_slots`` / ``degree`` fields — e.g. a
    ``kernels.secure_agg.SessionMeta``); ``perm`` is the host-readable
    random-graph permutation the kernel's neighbour table was built from
    (the oracle enumerates neighbours in Python, so it takes the
    permutation, not the table).  ``u_offset`` shifts the
    stochastic-rounding stream to the chunk's global flat offset; the mask
    stream stays chunk-local.
    """
    (D,) = x.shape
    xf = x.astype(jnp.float32) * scale
    floor = jnp.floor(xf)
    bit = (prf_uniforms(D, uniform_key_words, u_offset)
           < (xf - floor)).astype(jnp.float32)
    q = (floor + bit).astype(jnp.int32)
    return q + prf_session_mask(D, slot, session.num_slots,
                                session.key_words, session.degree, perm)


def rotate_quantize_prf(x: jnp.ndarray, scale: float, op_key_words,
                        uniform_key_words, u_offset: int = 0,
                        block: int = 512) -> jnp.ndarray:
    """Oracle for the fused sketch encode: q(scale * H(signs ⊙ x)).

    Deliberately an independent formulation — the ±1 diagonal one
    TAG_SIGN word per position, the Walsh–Hadamard butterfly as explicit
    per-element GATHERS (each stage reads its two operands by index
    arithmetic rather than the lane rotations the kernel and
    ``core.fl.compression.fwht`` both use; the per-element float ops are
    the same single add/sub, so the result is bit-identical while the
    indexing is derived independently), stochastic-rounding uniforms one
    TAG_UNIFORM word per position at the chunk's global offset.  Returns
    the full operator-domain vector, Hadamard pad included, matching the
    kernel's output length.
    """
    (D,) = x.shape
    full = -(-D // block) * block
    o0, o1 = jnp.asarray(op_key_words, prf.U32)
    e = jnp.arange(full)
    sbits = prf.stream_at(o0, o1, e, tag=prf.TAG_SIGN)
    signs = 1.0 - 2.0 * (sbits & 1).astype(jnp.float32)
    y = (jnp.pad(x.astype(jnp.float32), (0, full - D)) * signs
         ).reshape(full // block, block)
    idx = jnp.arange(block)
    h = 1
    while h < block:
        # position p = g*2h + s*h + t: stage output is a+b at s=0, a-b at
        # s=1, with a = y[g*2h + t], b = y[g*2h + h + t]
        g, s, t = idx // (2 * h), (idx // h) % 2, idx % h
        a, b = y[:, g * 2 * h + t], y[:, g * 2 * h + h + t]
        y = jnp.where(s == 0, a + b, a - b)
        h *= 2
    y = (y * jnp.float32(1.0 / math.sqrt(block))).reshape(full)
    yf = y * scale
    floor = jnp.floor(yf)
    bit = (prf_uniforms(full, uniform_key_words, u_offset)
           < (yf - floor)).astype(jnp.float32)
    return (floor + bit).astype(jnp.int32)


def weighted_quantize_accum_prf(x: jnp.ndarray, weights: jnp.ndarray,
                                uniforms: jnp.ndarray, scale: float,
                                session, perm=None) -> jnp.ndarray:
    """Oracle for the in-kernel PRF mask lane of the fused accumulation.

    ``session.slot_offset`` places row c at global session slot
    ``slot_offset + c`` (the sharded-tier case where one leaf holds a
    contiguous slice of a larger session's slots); rows beyond
    ``session.num_slots`` are not session members and carry no mask.
    """
    C, D = x.shape
    num_slots, offset = session.num_slots, int(session.slot_offset)
    masks = jnp.stack([
        prf_session_mask(D, offset + s, num_slots, session.key_words,
                         session.degree, perm)
        if offset + s < num_slots else jnp.zeros((D,), jnp.int32)
        for s in range(C)])
    return weighted_quantize_accum(x, weights, uniforms, scale, masks=masks)


# --- bitagg -------------------------------------------------------------------
def bit_counts(values: jnp.ndarray, thresholds: jnp.ndarray,
               uniforms: jnp.ndarray, flip_prob: float) -> jnp.ndarray:
    """Threshold-bit vote counts with randomized response.

    values: (N, F); thresholds: (T,); uniforms: (N, F, T) two-in-one draws —
    u < flip_prob/2 forces 1, u in [flip_prob/2, flip_prob) forces 0.
    Returns counts (F, T) f32.
    """
    bits = (values[..., None] <= thresholds).astype(jnp.float32)
    force1 = (uniforms < flip_prob / 2.0).astype(jnp.float32)
    keep = (uniforms >= flip_prob).astype(jnp.float32)
    bits_rr = force1 + keep * bits
    return bits_rr.sum(axis=0)


# --- flash_decode -------------------------------------------------------------
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 slot_pos: jnp.ndarray, pos, window) -> jnp.ndarray:
    """Single-token windowed decode attention (per batch row).

    q: (H, hd) scaled queries; k, v: (W, KV, hd); slot_pos: (W,) int32;
    pos: scalar int32.  GQA via head grouping.  Returns (H, hd) f32.
    """
    H, hd = q.shape
    W, KV, _ = k.shape
    rep = H // KV
    qg = q.reshape(KV, rep, hd).astype(jnp.float32)
    scores = jnp.einsum("grk,sgk->grs", qg, k.astype(jnp.float32))
    valid = (slot_pos >= 0) & (slot_pos <= pos)
    if window is not None:
        valid &= (pos - slot_pos) < window
    scores = jnp.where(valid[None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("grs,sgk->grk", probs, v.astype(jnp.float32))
    return out.reshape(H, hd)
