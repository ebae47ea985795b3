"""Secure aggregation: fixed-point quantization + pairwise additive masking.

Semantics (Bonawitz et al.-style, as run inside the paper's TEE): each client
encodes its clipped update into fixed-point int32, adds pairwise masks that
cancel in the sum, and the server recovers only the modular sum.  Because
int32 addition wraps (mod 2^32), the masked sum equals the unmasked sum
*exactly* — which is why the jitted round step can aggregate the quantized
ints directly with a psum while this module exercises the full masked
protocol end-to-end (tests assert bit-exact agreement).

``MaskSession`` is the first-class session object the engines consume: one
value carrying (key, slot range, graph degree, permutation, field modulus)
with traceable mask/recovery methods, so no engine threads those as loose
arguments.  Under it, three function layers live here:

  1. scalar codec — ``quantize`` / ``dequantize`` with a wraparound-window
     re-centering for decoded *sums* (``count``): the secure-agg field is
     ``field_modulus(bits, count)``, a power of two dividing 2^32, so sums
     whose int32 accumulation wrapped are still recovered exactly as long as
     the true sum fits the window (``|s| < C/2``).  ``to_field`` reduces a
     masked value to its canonical wire residue for reduced-field transports.
  2. host-side pairwise masks — ``pairwise_mask`` / ``mask_update`` /
     ``aggregate_masked`` (arbitrary peer-id sets, integer seeds).
  3. session masks — ``session_mask`` / ``session_masks`` /
     ``recovery_mask``: the jit-traceable variant keyed by a PRNGKey and a
     slot index, used *inside* the jitted engines (core/fl/aggregation.py
     writes masked vectors straight into the async buffer; core/fl/round.py
     masks the sync chunk scan).  When a session contributor drops,
     ``recovery_mask`` is the sum of the absent slots' masks — exactly the
     cancelling shares the surviving clients reconstruct in the real
     protocol — and adding it to the modular sum makes ``dequantize`` yield
     the true sum of the survivors.

Every mask in layers 2 and 3 is one stream of the counter-based pairwise
PRF in ``repro.kernels.prf`` (Threefry-2x32, keyed by session key and the
unordered slot pair, indexed by flat element position).  Random access by
element position is what lets the Pallas kernels in
``repro.kernels.secure_agg`` regenerate any tile of any mask on the fly in
VMEM — bit-identical to the host functions here, which serve as the oracle —
so the fused paths never materialize a (B, D) mask array in HBM.  Host-side
generation is batched: one vectorized PRF call per mask (``session_mask``),
one deduplicated pair sweep for a whole session (``session_masks``), and one
gated pair sweep for dropout recovery (``recovery_mask``) — no Python loops
over slots, O(num_slots * D) peak memory.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels import prf


def quantize(x: jnp.ndarray, bits: int, value_range: float,
             rng=None) -> jnp.ndarray:
    """Fixed-point encode to int32: x in [-range, range] -> int levels.

    With `rng`, stochastic rounding (unbiased); else round-to-nearest.
    """
    levels = jnp.float32(2 ** (bits - 1) - 1)
    scale = levels / value_range
    xf = jnp.clip(x.astype(jnp.float32), -value_range, value_range) * scale
    if rng is not None:
        floor = jnp.floor(xf)
        frac = xf - floor
        xf = floor + (jax.random.uniform(rng, x.shape) < frac).astype(jnp.float32)
    else:
        xf = jnp.round(xf)
    return xf.astype(jnp.int32)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def field_modulus(bits: int, count: int = 1) -> int:
    """The secure-agg field size for a ``count``-contribution sum.

    Smallest power of two >= count * 2^bits, capped at 2^32.  Powers of two
    <= 2^32 divide the int32 wraparound modulus, so a sum accumulated with
    plain int32 arithmetic (mod 2^32) can be reduced to its mod-C residue —
    the property ``dequantize(count=...)`` relies on.
    """
    return min(_next_pow2(count) * (1 << bits), 1 << 32)


def to_field(q: jnp.ndarray, modulus: int) -> jnp.ndarray:
    """Canonical unsigned residue of ``q`` in the secure-agg field, as int32.

    For ``modulus == 2^32`` the int32 two's-complement bit pattern *is* the
    residue; for smaller (power-of-two) fields the result lies in
    ``[0, modulus)`` — the reduced wire format that lets a masked value
    travel in ``log2(modulus)`` bits instead of 32.
    """
    if modulus >= 1 << 32:
        return q.astype(jnp.int32)
    assert modulus & (modulus - 1) == 0, "field modulus must be a power of two"
    # bitwise AND == mod for power-of-two fields, and (unlike jnp.mod with a
    # python-int divisor) representable when modulus is 2^31
    return q.astype(jnp.int32) & (modulus - 1)


def dequantize(q: jnp.ndarray, bits: int, value_range: float,
               count: int = 1) -> jnp.ndarray:
    """Decode an (aggregated) fixed-point tensor back to f32.

    count: number of summed contributions.  The decoded sum is re-centered
    into the wraparound window ``[-C/2, C/2)`` with
    ``C = field_modulus(bits, count)``: an int32 accumulation that wrapped
    (e.g. thousands of reduced-field residues) still round-trips exactly,
    because C divides 2^32 so the mod-2^32 representative determines the
    mod-C residue.
    """
    levels = jnp.float32(2 ** (bits - 1) - 1)
    q = recenter(q, field_modulus(bits, count))
    return q.astype(jnp.float32) * (value_range / levels)


def recenter(q: jnp.ndarray, modulus: int) -> jnp.ndarray:
    """Signed wraparound-window representative of a mod-``modulus`` sum.

    Maps any int32 representative of a mod-C residue into ``[-C/2, C/2)``.
    For ``modulus == 2^32`` this is the identity (the int32 bit pattern is
    already the signed representative).  ``q + half`` may wrap int32; that
    wrap is mod 2^32 and C | 2^32, so the mod-C reduction is unaffected.
    ``& (C-1)`` == mod C for the power-of-two field and stays
    int32-representable up to C == 2^31.
    """
    if modulus >= 1 << 32:
        return q.astype(jnp.int32)
    half = modulus // 2
    return ((q.astype(jnp.int32) + half) & (modulus - 1)) - half


# ---------------------------------------------------------------------------
# Wire codec — canonical residues bit-packed into a dense uint32 stream
# ---------------------------------------------------------------------------
def wire_bits(modulus: int) -> int:
    """Residue width of the packed wire format: ``ceil(log2(modulus))``.

    The field is a power of two (``field_modulus``), so every canonical
    residue fits exactly ``log2(modulus)`` bits — e.g. the bits=16, B=8
    field 2^19 ships 19-bit residues instead of 32-bit words.
    """
    if modulus >= 1 << 32:
        return 32
    if modulus < 2 or modulus & (modulus - 1):
        raise ValueError(f"wire width needs a power-of-two field modulus >= 2,"
                         f" got {modulus}")
    return (modulus - 1).bit_length()


def packed_words(size: int, modulus: int) -> int:
    """uint32 words in the packed stream of ``size`` residues."""
    return -(-size * wire_bits(modulus) // 32)


def pack_residues(q: jnp.ndarray, modulus: int) -> jnp.ndarray:
    """Bit-pack canonical field residues into the dense uint32 wire stream.

    ``q`` is an int32 array of residues along its LAST axis (what
    ``to_field`` produces); the result replaces that axis of ``size``
    elements with ``ceil(size * wire_bits / 32)`` uint32 words.  Layout is
    little-endian within the bit stream: element ``e`` occupies bit
    positions ``[e*w, (e+1)*w)`` of the concatenated stream (``w =
    wire_bits(modulus)``), and word ``k`` holds stream bits
    ``[32k, 32k+32)``.  32 consecutive elements therefore fill exactly
    ``w`` words, which is the static group the vectorized loop (and the
    Pallas kernel mirroring it) packs at once.  Exact round-trip for every
    power-of-two modulus <= 2^32, including the 2^31 / 2^32 edges (at the
    full field the stream is the uint32 reinterpretation of the int32
    row — same byte count, no-op reduction).
    """
    bits = wire_bits(modulus)
    if bits == 32:  # one residue per word: the row's own bits
        return jax.lax.bitcast_convert_type(q.astype(jnp.int32), jnp.uint32)
    size = q.shape[-1]
    nwords = packed_words(size, modulus)
    mask = jnp.uint32((1 << bits) - 1)
    v = q.astype(jnp.uint32) & mask
    groups = -(-size // 32)
    pad = groups * 32 - size
    if pad:
        v = jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, pad)])
    g = v.reshape(v.shape[:-1] + (groups, 32))
    cols = [jnp.zeros(g.shape[:-1], jnp.uint32) for _ in range(bits)]
    for j in range(32):  # static: each element lands in <= 2 words
        w0, shift = divmod(j * bits, 32)
        cols[w0] = cols[w0] | (g[..., j] << shift)
        if shift + bits > 32:  # straddles into the next word
            cols[w0 + 1] = cols[w0 + 1] | (g[..., j] >> (32 - shift))
    words = jnp.stack(cols, axis=-1).reshape(g.shape[:-2] + (groups * bits,))
    return words[..., :nwords]


def unpack_residues(words: jnp.ndarray, size: int,
                    modulus: int) -> jnp.ndarray:
    """Inverse of :func:`pack_residues`: wire words back to int32 residues.

    ``words`` carries ``packed_words(size, modulus)`` uint32 words along
    its last axis; returns the ``size`` canonical residues as int32 (the
    ``to_field`` convention), ready to re-enter the mod-2^32 accumulation
    path — exact because the field divides 2^32.
    """
    bits = wire_bits(modulus)
    nwords = packed_words(size, modulus)
    if words.shape[-1] != nwords:
        raise ValueError(
            f"packed stream of {words.shape[-1]} words does not match "
            f"{size} residues of a {modulus}-modulus field "
            f"({bits}-bit wire -> {nwords} words); was this row packed "
            f"under a different session field?")
    if bits == 32:
        return jax.lax.bitcast_convert_type(words, jnp.int32)
    mask = jnp.uint32((1 << bits) - 1)
    groups = -(-size // 32)
    pad = groups * bits - nwords
    if pad:
        words = jnp.pad(words, [(0, 0)] * (words.ndim - 1) + [(0, pad)])
    w = words.reshape(words.shape[:-1] + (groups, bits))
    elems = []
    for j in range(32):  # static: each element reads <= 2 words
        w0, shift = divmod(j * bits, 32)
        v = w[..., w0] >> shift
        if shift + bits > 32:
            v = v | (w[..., w0 + 1] << (32 - shift))
        elems.append(v & mask)
    out = jnp.stack(elems, axis=-1).reshape(w.shape[:-2] + (groups * 32,))
    return out[..., :size].astype(jnp.int32)


# ---------------------------------------------------------------------------
# Pairwise-PRF mask generation (batched; one vectorized sweep per mask set)
# ---------------------------------------------------------------------------
def _size(shape) -> int:
    n = 1
    for s in shape:
        n *= int(s)
    return n


def effective_degree(num_slots: int, degree: int) -> int:
    """Canonicalize a mask-graph degree: 0 == complete graph.

    A k-regular degree must be even (each slot pairs with k/2 neighbours on
    each side of the — possibly permuted — ring) and leave at least one
    non-neighbour (k <= num_slots - 2); anything denser collapses to the
    complete graph.
    """
    if degree <= 0 or degree >= num_slots - 1:
        return 0
    if degree % 2 != 0:
        raise ValueError(f"ring mask-graph degree must be even, got {degree}")
    return degree


# fold-in tag deriving the per-session neighbourhood permutation key from the
# session key (disjoint from the 0x5E55/0x7EE/0xDEE engine stream tags)
GRAPH_PERM_TAG = 0x6B52


def session_perm(num_slots: int, key) -> jnp.ndarray:
    """The session's random neighbourhood permutation — Bell et al. style.

    SecAgg+ draws a RANDOM k-regular session graph, not a circulant one:
    our construction relabels the k-ring by a permutation drawn from the
    session key (edge set {{perm[i], perm[(i+j) % n]}}), which is k-regular
    for every even k and resampled every session — a colluding server
    cannot steer who masks with whom.  Traceable (usable inside the jitted
    engines); the same permutation must be threaded to every consumer of
    the session's masks (encode, recovery, kernels) for cancellation.
    """
    pkey = jax.random.fold_in(key, GRAPH_PERM_TAG)
    return jax.random.permutation(pkey, num_slots).astype(jnp.int32)


def _neighbor_slots(slot, num_slots: int, degree: int,
                    perm=None) -> jnp.ndarray:
    """The slots ``slot`` shares a pairwise mask with, traceable in slot.

    Complete graph (degree 0): all num_slots - 1 other slots, enumerated
    without the diagonal (``others = arange + (arange >= slot)``).  Degree
    k: the k/2 neighbours on each side of the ring — circulant
    ``(slot +- j) % num_slots`` when ``perm`` is None, or the
    ``session_perm``-relabelled ring ``perm[(perm^-1[slot] +- j) % n]``
    (the random k-regular graph) when given.
    """
    slot = jnp.asarray(slot, jnp.int32)
    k = effective_degree(num_slots, degree)
    if k == 0:
        d = jnp.arange(num_slots - 1, dtype=jnp.int32)
        return d + (d >= slot).astype(jnp.int32)
    offs = jnp.asarray([j for j in range(1, k // 2 + 1)]
                       + [-j for j in range(1, k // 2 + 1)], jnp.int32)
    if perm is None:
        return (slot + offs + num_slots) % num_slots
    perm = jnp.asarray(perm, jnp.int32)
    inv = jnp.argsort(perm).astype(jnp.int32)
    return perm[(inv[slot] + offs + num_slots) % num_slots]


def neighbor_table(num_slots: int, degree: int, perm=None):
    """All slots' mask-graph neighbours as one (num_slots, k) int32 table.

    ``None`` for complete graphs (degree 0 — static in-kernel enumeration
    needs no table).  This is the form the Pallas kernels consume for the
    random k-regular graph: the table is tiny (num_slots * k words) and
    rides the kernels' scalar meta operand.
    """
    k = effective_degree(num_slots, degree)
    if k == 0:
        return None
    slots = jnp.arange(num_slots, dtype=jnp.int32)
    return jax.vmap(
        lambda s: _neighbor_slots(s, num_slots, degree, perm))(slots)


def session_pairs(num_slots: int, degree: int = 0, perm=None):
    """The mask graph's edge list as (lo, hi) int32 arrays (static shape).

    Complete graph: all num_slots*(num_slots-1)/2 unordered pairs.  Degree
    k: the num_slots*k/2 ring edges {s, (s+j) % num_slots}, j = 1..k/2 —
    relabelled through ``perm`` (the random k-regular session graph) when
    given, in which case the arrays are traced values of static shape.
    """
    k = effective_degree(num_slots, degree)
    if k == 0:
        lo, hi = jnp.triu_indices(num_slots, k=1)
        return lo.astype(jnp.int32), hi.astype(jnp.int32)
    s = jnp.arange(num_slots, dtype=jnp.int32)
    if perm is None:
        edges = jnp.stack([jnp.stack([s, (s + j) % num_slots], axis=1)
                           for j in range(1, k // 2 + 1)]).reshape(-1, 2)
    else:
        p = jnp.asarray(perm, jnp.int32)
        edges = jnp.stack([jnp.stack([p[s], p[(s + j) % num_slots]], axis=1)
                           for j in range(1, k // 2 + 1)]).reshape(-1, 2)
    return jnp.min(edges, axis=1), jnp.max(edges, axis=1)


def _edge_chunks(lo: jnp.ndarray, hi: jnp.ndarray, D: int, w=None):
    """Pad an edge list into fixed-size chunks for a lax.scan sweep.

    Returns (lo, hi, weight) each shaped (n_chunks, chunk); padded entries
    alias edge (0, 0) and carry weight 0, so every sweep body can neutralize
    them the same way.  ``w`` (int32 0/1 per edge, default all-1) lets a
    caller pass an already-padded edge partition — the hierarchy tier's
    per-leaf shard of the session edge list.  The chunk size balances scan
    length against cache footprint: at least 16 edges per chunk (short
    scans — a chunked scatter over few-edge chunks rewrites the whole
    accumulator per step), at most ~16 MiB of stream words.
    """
    P = int(lo.shape[0])
    chunk = max(1, min(P, max((1 << 22) // max(D, 1), 16)))
    n_chunks = -(-P // chunk)
    pad = n_chunks * chunk - P
    if w is None:
        w = jnp.ones((P,), jnp.int32)
    w = jnp.concatenate([w.astype(jnp.int32), jnp.zeros((pad,), jnp.int32)])
    lo_c = jnp.concatenate([lo, jnp.zeros((pad,), jnp.int32)])
    hi_c = jnp.concatenate([hi, jnp.zeros((pad,), jnp.int32)])
    return (lo_c.reshape(n_chunks, chunk), hi_c.reshape(n_chunks, chunk),
            w.reshape(n_chunks, chunk))


def _signed_pair_sum(k0, k1, slot, others, shape) -> jnp.ndarray:
    """sum_d sign(d - slot) * PRF_stream(key, pair(slot, d)) over ``others``.

    One batched PRF call generates all pair streams ((len(others), D) peak);
    a diagonal entry d == slot (allowed in ``pairwise_mask``'s peer list)
    gates itself out via sign 0.  Traceable in ``slot`` and in ``others``.
    """
    slot = jnp.asarray(slot, jnp.int32)
    others = jnp.asarray(others, jnp.int32)
    lo = jnp.minimum(slot, others)
    hi = jnp.maximum(slot, others)
    pk0, pk1 = prf.pair_keys(k0, k1, lo.astype(prf.U32), hi.astype(prf.U32))
    m = prf.stream_block(pk0, pk1, _size(shape))  # (len(others), D)
    sign = jnp.sign(others - slot)  # +1 below, -1 above, 0 on the diagonal
    total = jnp.sum(sign[:, None] * m, axis=0, dtype=jnp.int32)  # mod 2^32
    return total.reshape(shape)


def pairwise_mask(shape, client_id: int, peer_ids: Sequence[int],
                  seed: int) -> jnp.ndarray:
    """Additive int32 mask for `client_id` that cancels over all clients.

    mask_c = sum_{d > c} PRF(c, d) - sum_{d < c} PRF(d, c): each unordered
    pair contributes +m to one endpoint and -m to the other, so
    sum_c mask_c == 0 (mod 2^32).  All peers are generated in ONE batched
    PRF sweep — trace size is O(1) in the peer count (the old per-peer
    fold-in loop emitted O(B) ops and blew up trace time at B=64).
    """
    k0, k1 = prf.key_words(jax.random.PRNGKey(seed))
    return _signed_pair_sum(k0, k1, client_id, jnp.asarray(peer_ids), shape)


def mask_update(q: jnp.ndarray, client_id: int, peer_ids: Sequence[int],
                seed: int) -> jnp.ndarray:
    return q + pairwise_mask(q.shape, client_id, peer_ids, seed)


def aggregate_masked(masked: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Modular sum of masked contributions — masks cancel exactly.

    One stacked wraparound reduce (trace O(1) in the contribution count).
    """
    return jnp.sum(jnp.stack(list(masked)), axis=0, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Session masks — the jit-traceable variant used inside the engines
# ---------------------------------------------------------------------------
def session_mask(shape, slot, num_slots: int, key,
                 degree: int = 0, perm=None) -> jnp.ndarray:
    """Pairwise mask for session position ``slot`` of ``num_slots``.

    Same cancellation identity (and same PRF tree — bit-identical when
    ``key == jax.random.PRNGKey(seed)``) as ``pairwise_mask`` over
    ``peer_ids=range(num_slots)``, but keyed by a PRNGKey — so the host can
    fold a per-session id in — and traceable in ``slot``, which is what lets
    the jitted buffer-write path mask a contribution for whatever slot it
    lands in without per-slot recompilation.  ``degree`` selects the mask
    graph (0 = complete, even k = k-regular); ``perm`` (``session_perm``)
    relabels the k-ring into the random k-regular graph.  This is the host
    oracle for the in-kernel PRF mask lanes (kernels/secure_agg.py):
    parity is bit-exact and test-enforced.
    """
    k0, k1 = prf.key_words(key)
    return _signed_pair_sum(
        k0, k1, slot, _neighbor_slots(slot, num_slots, degree, perm), shape)


def session_masks(shape, num_slots: int, key, degree: int = 0,
                  perm=None) -> jnp.ndarray:
    """All ``num_slots`` session masks at once -> (num_slots, *shape) int32.

    Two bit-identical strategies (int32 addition commutes mod 2^32):

      * small complete-graph sessions (<= 32 slots): per-row batched
        generation — each row's neighbour streams fuse straight into its
        signed sum, so no stream is ever materialized (the XLA analogue of
        the in-kernel tile lane), at the cost of generating each edge
        stream twice (measured faster than the sweep at these sizes);
      * everything else: deduplicated edge sweep over ``session_pairs`` —
        each unordered pair stream is generated ONCE and scatter-added
        (+ to its low slot, - to its high slot), in chunks bounded to
        ~16 MiB of stream, so peak memory stays O(num_slots * D).
    """
    D = _size(shape)
    k0, k1 = prf.key_words(key)
    if num_slots <= 32 and effective_degree(num_slots, degree) == 0:
        rows = [_signed_pair_sum(
            k0, k1, s, _neighbor_slots(jnp.int32(s), num_slots, degree),
            (D,)) for s in range(num_slots)]
        return jnp.stack(rows).reshape((num_slots,) + tuple(shape))
    lo, hi = session_pairs(num_slots, degree, perm)
    out = jnp.zeros((num_slots, D), jnp.int32)
    if int(lo.shape[0]) == 0:
        return out.reshape((num_slots,) + tuple(shape))

    def body(acc, xs):
        clo, chi, cw = xs
        pk0, pk1 = prf.pair_keys(k0, k1, clo.astype(prf.U32),
                                 chi.astype(prf.U32))
        m = prf.stream_block(pk0, pk1, D) * cw[:, None]  # (chunk, D)
        acc = acc.at[clo].add(m).at[chi].add(-m)  # wraps mod 2^32
        return acc, None

    out, _ = jax.lax.scan(body, out, _edge_chunks(lo, hi, D))
    return out.reshape((num_slots,) + tuple(shape))


def recovery_sweep(shape, present, lo, hi, key, w=None) -> jnp.ndarray:
    """Gated pairwise-stream sweep over an EXPLICIT edge list.

    The recovery primitive: sums ``(present[hi] - present[lo]) *
    stream(lo, hi)`` over the given edges — an edge with both endpoints
    present or both absent gates itself to zero, so only mixed edges
    contribute, and each contributing edge stream is generated exactly
    once.  ``w`` (0/1 per edge) neutralizes padding edges; partial sums
    over disjoint edge partitions add up (mod 2^32) to the full sweep
    bit-exactly, which is what lets the hierarchy tier split one session's
    recovery across leaves and ``psum`` the partials.
    """
    present = jnp.asarray(present).astype(jnp.int32).reshape(-1)
    D = _size(shape)
    k0, k1 = prf.key_words(key)
    if int(lo.shape[0]) == 0:
        return jnp.zeros(shape, jnp.int32)

    def body(acc, xs):
        clo, chi, cw = xs
        # 0 unless exactly one endpoint absent (and 0 on padded edges)
        gate = (present[chi] - present[clo]) * cw
        pk0, pk1 = prf.pair_keys(k0, k1, clo.astype(prf.U32),
                                 chi.astype(prf.U32))
        m = prf.stream_block(pk0, pk1, D)  # (chunk, D)
        return acc + jnp.sum(gate[:, None] * m, axis=0, dtype=jnp.int32), None

    total, _ = jax.lax.scan(body, jnp.zeros((D,), jnp.int32),
                            _edge_chunks(lo, hi, D, w))
    return total.reshape(shape)


def recovery_mask(shape, present, num_slots: int, key,
                  degree: int = 0, perm=None) -> jnp.ndarray:
    """Sum of the session masks of the ABSENT slots — the dropout shares.

    ``present``: (num_slots,) 1/0 (or bool) per slot — 1 for contributors
    whose masked vector made it into the aggregate.  Since all ``num_slots``
    masks sum to zero, the surviving contributions carry exactly
    ``-sum_{absent} mask_s`` of un-cancelled mask; adding this recovery term
    to the modular sum restores the true sum of the survivors.  In the real
    protocol the surviving clients reconstruct these shares from the dropped
    clients' Shamir-shared seeds; in the simulator the server (which knows
    the session key) stands in for them.

    One gated edge sweep (``recovery_sweep``) over the session graph's
    edges instead of the old num_slots nested ``session_mask`` calls.
    Edge chunks are bounded to ~16 MiB of stream; peak memory is
    O(num_slots * D) and trace size is O(1) in the session size.
    """
    lo, hi = session_pairs(num_slots, degree, perm)
    return recovery_sweep(shape, present, lo, hi, key)


# ---------------------------------------------------------------------------
# MaskSession — the first-class session object every engine consumes
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MaskSession:
    """One pairwise-mask session, as a value.

    Everything a consumer needs to generate, cancel, or recover this
    session's masks travels together: the PRNG ``key`` (which roots every
    pair stream), the session size ``num_slots``, the mask-graph ``degree``
    (canonical: 0 = complete, even k = k-regular), the optional random
    k-regular relabelling ``perm`` (``session_perm``; None = circulant /
    complete), the first slot ``slot_offset`` of the consumer's row range
    (a SHARD of the session — 0 for whole-session consumers), and the
    secure-agg field ``modulus``.  Replaces the loose
    slot_offset/num_slots/mask_key/perm/degree threading that used to run
    through every engine builder and kernel wrapper.

    Registered as a jax pytree: ``key``/``perm``/``slot_offset`` are traced
    data (sessions are built inside jitted steps from the round's rng),
    ``num_slots``/``degree``/``modulus`` are static metadata.  All methods
    are traceable and bit-identical to the free functions they wrap — the
    in-kernel PRF lanes (``repro.kernels.secure_agg``) consume the same
    fields through their ``SessionMeta`` view and are oracle-checked
    against these.
    """

    key: Any  # PRNGKey rooting every pair stream of the session
    num_slots: int  # static session size
    degree: int = 0  # static canonical graph degree (0 = complete)
    perm: Optional[jnp.ndarray] = None  # random k-regular relabelling
    slot_offset: Any = 0  # first slot of this consumer's row range
    modulus: int = 1 << 32  # secure-agg field (power of two, divides 2^32)

    # -- derived views ------------------------------------------------------
    def key_words(self):
        """(k0, k1) uint32 PRF key words (the kernels' wire format)."""
        return prf.key_words(self.key)

    def neighbor_table(self) -> Optional[jnp.ndarray]:
        """(num_slots, degree) table for the kernels' scalar-meta lane, or
        None when the graph is static (complete / circulant ring)."""
        if self.perm is None:
            return None
        return neighbor_table(self.num_slots, self.degree, self.perm)

    def edges(self):
        """The session graph's (lo, hi) edge list (``session_pairs``)."""
        return session_pairs(self.num_slots, self.degree, self.perm)

    # -- mask generation ----------------------------------------------------
    def mask(self, shape, slot) -> jnp.ndarray:
        """The pairwise mask of ABSOLUTE session position ``slot``."""
        return session_mask(shape, slot, self.num_slots, self.key,
                            self.degree, self.perm)

    def masks(self, shape) -> jnp.ndarray:
        """All ``num_slots`` masks at once (one deduplicated sweep)."""
        return session_masks(shape, self.num_slots, self.key, self.degree,
                             self.perm)

    def recovery(self, shape, present) -> jnp.ndarray:
        """Sum of the ABSENT slots' masks — the dropout-recovery shares."""
        return recovery_mask(shape, present, self.num_slots, self.key,
                             self.degree, self.perm)

    @property
    def wire_bits(self) -> int:
        """Residue width of this session's packed wire format."""
        return wire_bits(self.modulus)

    def reduce(self, q: jnp.ndarray) -> jnp.ndarray:
        """``q`` in WIRE FORMAT: canonical field residues, bit-packed.

        The single choke point that decides the wire width — the session's
        ``modulus`` (the ENGINE field, shared by every leaf session of a
        tree) fixes the residue width, so a (..., size) int32 row leaves as
        ``packed_words(size, modulus)`` dense uint32 words.  At the full
        2^32 field this is the uint32 reinterpretation (no reduction, same
        bytes)."""
        return pack_residues(to_field(q, self.modulus), self.modulus)

    def expand(self, words: jnp.ndarray, size: int) -> jnp.ndarray:
        """Inverse of :meth:`reduce`: wire words back to int32 residues."""
        return unpack_residues(words, size, self.modulus)


jax.tree_util.register_dataclass(
    MaskSession,
    data_fields=("key", "perm", "slot_offset"),
    meta_fields=("num_slots", "degree", "modulus"))


def make_session(key, num_slots: int, *, degree: int = 0,
                 random_graph: bool = False, slot_offset=0,
                 modulus: int = 1 << 32) -> MaskSession:
    """Build a :class:`MaskSession` with canonical graph parameters.

    ``degree`` is canonicalized against ``num_slots``
    (``effective_degree``: sessions too small for the requested k-regular
    graph clamp to the complete graph — see the README's small-B collusion
    note), and the random k-regular relabelling is drawn here from the
    session key when ``random_graph`` — so every consumer derived from the
    same key sees the same graph.  Traceable in ``key``/``slot_offset``.
    """
    k = effective_degree(num_slots, degree)
    perm = session_perm(num_slots, key) if (k > 0 and random_graph) else None
    return MaskSession(key=key, num_slots=num_slots, degree=k, perm=perm,
                       slot_offset=slot_offset, modulus=modulus)


def secure_aggregate(updates: Sequence[jnp.ndarray], bits: int,
                     value_range: float, seed: int = 0,
                     rng=None) -> jnp.ndarray:
    """Full protocol: quantize -> mask -> modular sum -> dequantize.

    Returns the *mean* of the updates (weighted averaging with equal weights;
    the round step handles non-uniform weights by pre-scaling).
    """
    n = len(updates)
    peer_ids = list(range(n))
    masked = []
    for c, u in enumerate(updates):
        r = None if rng is None else jax.random.fold_in(rng, c)
        q = quantize(u, bits, value_range, r)
        masked.append(mask_update(q, c, peer_ids, seed))
    total = aggregate_masked(masked)
    return dequantize(total, bits, value_range, count=n) / n
