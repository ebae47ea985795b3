"""The unified jitted aggregation engine — one code path for sync and async.

Every aggregate this system produces (a synchronous DP-FL round over a
cohort, or a buffered-asynchronous FedBuff apply over a staleness-tagged
buffer) is the same pointwise pipeline:

  1. per-contribution L2 clip (DP-SGD sensitivity bound);
  2. in ``device`` noise placement, per-contribution Gaussian noise;
  3. contribution weighting (data weight for sync, staleness discount for
     async) — applied *before* fixed-point encoding so the weighted sum is
     what travels through the secure-aggregation field;
  4. fixed-point int32 encode with stochastic rounding + wraparound sum —
     bit-identical to the pairwise-masked secure-agg sum (masks cancel; see
     core/fl/secure_agg.py for the full protocol);
  5. decode, divide by the total weight, and in ``tee`` placement add one
     Gaussian draw to the aggregate inside the trusted boundary.

``AggregationSpec`` captures the static parameters of that pipeline so both
engines share the exact arithmetic; the tree-shaped helpers serve the sync
round's chunked scan (core/fl/round.py) and the flat ``aggregate_buffer``
serves the async engine's stacked (B, D) device buffer (core/fl/async_fl.py),
optionally through the fused Pallas kernels in repro/kernels.  Pairwise
masking always travels as a first-class ``secure_agg.MaskSession``
(built here via ``make_mask_session`` so the graph degree/permutation stay
aligned with the spec); kernels consume it through ``_kernel_session``'s
``SessionMeta`` view.

The engines are PYTREE-NATIVE through a :class:`ParamPlan`: a static,
hashable description of how a model pytree's leaves map onto flat CHUNKS
(consecutive whole leaves grouped up to ``FLConfig.param_chunk_elems``
elements, padded to kernel block multiples).  Every chunk runs its own
mask session (key derived per chunk by ``fold_in`` from the engine session
key) and its own slice of the stochastic-rounding uniform stream (global
flat positions), so a multi-chunk engine never materializes the full (D,)
concatenation — and the single-chunk plan is the exact legacy flat engine,
bit for bit.  The global L2 clip still spans all leaves (the ``dp.py``
left-fold), which is what makes the encode chunk-INVARIANT: the same model
under any chunking decodes to the same aggregate.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.fl import compression as comp
from repro.core.fl import dp
from repro.core.fl import secure_agg as sa
from repro.kernels import prf
from repro.kernels.ops import on_tpu


class AggregationSpec(NamedTuple):
    """Static description of one aggregation — hashable, safe as a jit static.

    ``num_contributors`` is the design size of the aggregate (cohort size for
    sync rounds, buffer size for async): it bounds the fixed-point sum so a
    full aggregate cannot wrap int32, and scales the TEE noise draw.
    """

    num_contributors: int
    clip_norm: float
    use_secure_agg: bool
    sa_scale: float  # fixed-point scale (1.0 when secure agg is off)
    dev_noise: float  # per-contribution Gaussian std ("device" placement)
    tee_noise: float  # aggregate-mean Gaussian std ("tee" placement)
    mask_degree: int = 0  # pairwise mask graph degree (0 = complete graph)
    # sparse-graph topology: random k-regular neighbourhoods drawn per
    # session from the session key (Bell et al.), vs the circulant ring
    random_graph: bool = False
    # the secure-agg field of a full aggregate (power of two dividing
    # 2^32) — travels with every MaskSession so reduced-field transports
    # know the session's wire residue width
    field_modulus: int = 1 << 32
    # structured/sketched upload compression inside the masked field
    # (core/fl/compression.py).  The identity spec is the exact legacy
    # code path; active specs shrink every streamed wire/buffer width to
    # the compressed chunk sizes.
    compression: comp.CompressionSpec = comp.CompressionSpec()


def fixed_point_scale(fl_cfg, num_contributors: int) -> float:
    """Fixed-point scale such that a full-aggregate sum cannot wrap int32.

    Effective per-contribution levels = (2^(bits-1)-1)/n - 1 — the field must
    hold the sum including the stochastic-rounding carry bit, exactly as in
    deployed secure aggregation.
    """
    levels = (2 ** (fl_cfg.secure_agg_bits - 1) - 1) / num_contributors - 1.0
    return max(levels, 1.0) / fl_cfg.secure_agg_range


def make_spec(fl_cfg, num_contributors: int) -> AggregationSpec:
    use_sa = fl_cfg.secure_agg_bits > 0
    degree = sa.effective_degree(
        num_contributors, getattr(fl_cfg, "secure_agg_degree", 0))
    return AggregationSpec(
        num_contributors=num_contributors,
        clip_norm=fl_cfg.clip_norm,
        use_secure_agg=use_sa,
        sa_scale=fixed_point_scale(fl_cfg, num_contributors) if use_sa else 1.0,
        dev_noise=dp.noise_stddev(fl_cfg, num_contributors, "device")
        if fl_cfg.noise_placement == "device" else 0.0,
        tee_noise=dp.noise_stddev(fl_cfg, num_contributors, "tee")
        if fl_cfg.noise_placement == "tee" else 0.0,
        mask_degree=degree,
        random_graph=(degree > 0
                      and not getattr(fl_cfg, "secure_agg_circulant", False)),
        field_modulus=sa.field_modulus(fl_cfg.secure_agg_bits,
                                       num_contributors)
        if use_sa else 1 << 32,
        compression=comp.CompressionSpec(
            mode=getattr(fl_cfg, "compress_mode", "none"),
            rate=getattr(fl_cfg, "compress_rate", 1.0)),
    )


def make_mask_session(spec: AggregationSpec, key, *,
                      num_slots: Optional[int] = None,
                      slot_offset=0) -> Optional[sa.MaskSession]:
    """The :class:`secure_agg.MaskSession` of one aggregation, or None.

    One construction point keeps every consumer of a session's masks
    (client encode, tee lanes, recovery, kernels) aligned: the graph
    degree is canonicalized against the session size (``num_slots``
    defaults to the spec's contributor count; a leaf session of the
    two-level tier passes its own, smaller size) and the random k-regular
    relabelling (``spec.random_graph``) is drawn from the session key —
    so any two holders of the same key derive the SAME graph, which is
    what cancellation needs.  Traceable in ``key``/``slot_offset``.
    """
    if key is None:
        return None
    n = spec.num_contributors if num_slots is None else num_slots
    # the field is the ENGINE's (a leaf partial still combines into the
    # full aggregate at the root), so it does not shrink with num_slots
    return sa.make_session(key, n, degree=spec.mask_degree,
                           random_graph=spec.random_graph,
                           slot_offset=slot_offset,
                           modulus=spec.field_modulus)


def _kernel_session(session: sa.MaskSession):
    """The kernels' ``SessionMeta`` view of a protocol-layer session."""
    from repro.kernels import secure_agg as _ksa
    return _ksa.SessionMeta(
        key_words=jnp.stack(session.key_words()),
        num_slots=session.num_slots, degree=session.degree,
        slot_offset=session.slot_offset,
        neighbors=session.neighbor_table())


# ---------------------------------------------------------------------------
# Fixed-point secure-aggregation encode / decode (tree- and array-shaped)
# ---------------------------------------------------------------------------
def encode_array(x: jnp.ndarray, scale: float, rng) -> jnp.ndarray:
    """Stochastic-rounding fixed-point encode of one array to int32."""
    xf = x.astype(jnp.float32) * scale
    floor = jnp.floor(xf)
    frac = xf - floor
    bit = (jax.random.uniform(rng, x.shape) < frac).astype(jnp.float32)
    return (floor + bit).astype(jnp.int32)


def decode_tree(tree, scale: float):
    return jax.tree.map(lambda q: q.astype(jnp.float32) / scale, tree)


# ---------------------------------------------------------------------------
# Pairwise session masking (the in-engine secure-aggregation hot path)
# ---------------------------------------------------------------------------
def encode_masked_contribution(x: jnp.ndarray, weight, slot,
                               spec: AggregationSpec,
                               session: sa.MaskSession, rng, *,
                               use_pallas: bool = False):
    """The CLIENT side of the in-path masked protocol, on a flat delta.

    clip -> weight -> [device noise] -> stochastic fixed-point encode -> add
    the pairwise mask of ``slot`` (an ABSOLUTE position) in ``session``.
    This is the exact arithmetic of the unmasked ``aggregate_buffer`` row
    pipeline, so a masked buffer decodes to the same aggregate (up to
    independent stochastic-rounding draws).  The server only ever receives
    the returned masked int32 vector; the norm / clip indicator are
    client-side metrics (in production they ride the same secure channel as
    aggregated scalars).

    The encode+mask tail is one pass of the counter-based PRF pipeline:
    stochastic-rounding uniforms and the slot's pairwise session mask both
    come from ``repro.kernels.prf`` streams, so the host path here is
    bit-identical to the fused Pallas kernel (``quantize_mask_prf``) used
    when ``use_pallas`` — where mask and uniforms are generated in-kernel
    per VMEM tile and never exist in HBM.

    Returns (masked int32 (D,), pre-clip norm, was_clipped in {0., 1.}).
    """
    xw, nrm, was_clipped = _clip_weight_noise(x, weight, spec, rng)
    if use_pallas:
        from repro.kernels import secure_agg as _ksa
        u_words = prf.key_words(jax.random.fold_in(rng, 2))
        masked = _ksa.quantize_mask_prf(
            xw, spec.sa_scale, slot, jnp.stack(u_words),
            _kernel_session(session),
            interpret=not on_tpu())
    else:
        q = _stream_quantize(xw, spec.sa_scale, rng)
        masked = q + session.mask(xw.shape, slot)  # wraps mod 2^32
    return masked, nrm, was_clipped


def _clip_weight_noise(x: jnp.ndarray, weight, spec: AggregationSpec, rng):
    """The shared pre-encode prologue: clip -> weight -> [device noise].

    One implementation for the masked AND unmasked streaming encodes —
    their bit-parity contracts (streamed-off vs batched, sharded vs
    single-host) hinge on identical arithmetic and rng keying
    (``fold_in(rng, 1)`` is the noise stream), so it must not fork.

    Returns (xw (D,) f32 ready to quantize, pre-clip norm, was_clipped).
    """
    x = x.astype(jnp.float32)
    nrm = jnp.sqrt(jnp.sum(x * x))
    clip_scale = jnp.minimum(1.0, spec.clip_norm / jnp.maximum(nrm, 1e-12))
    weight = jnp.asarray(weight, jnp.float32)
    xw = x * (weight * clip_scale)
    if spec.dev_noise > 0.0:
        noise = jax.random.normal(jax.random.fold_in(rng, 1), x.shape,
                                  jnp.float32)
        xw = xw + noise * (spec.dev_noise * weight)
    return xw, nrm, (clip_scale < 1.0).astype(jnp.float32)


def _stream_quantize(xw: jnp.ndarray, sa_scale: float, rng) -> jnp.ndarray:
    """Stochastic fixed-point encode with PRF uniforms (``fold_in(rng, 2)``
    keys the TAG_UNIFORM stream — the same derivation as the fused Pallas
    push kernel, so host and kernel rows stay bit-identical)."""
    (D,) = xw.shape
    u_words = prf.key_words(jax.random.fold_in(rng, 2))
    xf = xw * sa_scale
    floor = jnp.floor(xf)
    bit = (prf.uniform_block(*u_words, D) < (xf - floor)).astype(jnp.float32)
    return (floor + bit).astype(jnp.int32)


def encode_contribution(x: jnp.ndarray, weight, spec: AggregationSpec, rng):
    """The UNMASKED streaming encode: clip -> weight -> [device noise] ->
    stochastic fixed-point encode of one flat delta, per arrival.

    The mask_mode="off" analogue of ``encode_masked_contribution`` — the
    identical pipeline (same helpers, same rng streams) minus the mask
    add, so the baseline async engine can stream its encode into the gaps
    between arrivals exactly like ``tee_stream`` does and pay a near-free
    flush (a plain modular sum).

    Returns (int32 (D,), pre-clip norm, was_clipped in {0., 1.}).
    """
    xw, nrm, was_clipped = _clip_weight_noise(x, weight, spec, rng)
    return _stream_quantize(xw, spec.sa_scale, rng), nrm, was_clipped


def aggregate_masked_buffer(mbuf: jnp.ndarray, present: jnp.ndarray,
                            total_weight, spec: AggregationSpec,
                            session: Optional[sa.MaskSession], rng, *,
                            recover: bool = True, masked: bool = True):
    """The SERVER side of the in-path masked protocol: modular sum + decode.

    mbuf:    (B, D) int32 — per-slot MASKED fixed-point contributions (what
             ``encode_masked_contribution`` produced); the server never sees
             anything else.
    present: (B,) 1/0 — slots whose contributor delivered.  Absent slots are
             gated out and their un-cancelled mask shares are re-added via
             the session's recovery sweep (dropout recovery), so the decode
             yields the exact sum of the survivors.
    session: the rows' :class:`secure_agg.MaskSession` (None allowed only
             when ``masked=False`` — there are no shares to recover).
    recover: static.  A session the caller KNOWS is complete (every slot
             delivered — the steady-state buffer apply) can skip both the
             present-gating and the recovery sweep: all pairwise masks
             cancel in the plain modular sum, bit-identically.  Partial
             flushes must pass ``recover=True``.
    masked:  static.  False = the buffer holds UNMASKED streamed encodings
             (the mask_mode="off" streaming engine): partial flushes still
             gate absent slots but there are no mask shares to recover.

    Returns the weight-normalized mean delta (D,) with TEE noise per
    ``finalize_aggregate``.
    """
    B, D = mbuf.shape
    if recover:
        pres_i = jnp.asarray(present).astype(jnp.int32)
        acc = jnp.sum(mbuf * pres_i[:, None], axis=0)  # int32, wraps mod 2^32
        if masked:
            acc = acc + session.recovery((D,), present)
    else:
        acc = jnp.sum(mbuf, axis=0)  # full session: masks cancel exactly
    # same TEE-noise stream derivation as aggregate_buffer
    return finalize_aggregate(acc, total_weight, spec,
                              jax.random.fold_in(rng, 0xDEE))


def ordered_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Sum of a (B,) f32 vector in one fixed pairwise order.

    ``jnp.sum`` leaves the association order of f32 adds to XLA, which
    picks it from the operand's layout and fusion, so the single-host
    engine and the tier (whose per-slot vectors arrive as (L, Bl) leaf
    shards) could round the same values differently.  Halving a
    zero-padded power-of-two vector fixes the order: every caller adds
    the same tree of pairs, whatever the layout.
    """
    n = x.shape[0]
    x = jnp.pad(x, (0, (1 << max(n - 1, 0).bit_length()) - n))
    while x.shape[0] > 1:
        h = x.shape[0] // 2
        x = x[:h] + x[h:]
    return x[0]


def slot_metrics(w: jnp.ndarray, norms: jnp.ndarray,
                 clips: jnp.ndarray) -> dict:
    """The weighted per-slot flush metrics every engine reports.

    ``w``: (B,) effective slot weights (zero for absent slots); ``norms``
    / ``clips``: (B,) pre-clip norms and clip indicators.  One
    implementation with :func:`ordered_sum`, so the tier's metrics equal
    the single host's to the bit.
    """
    w_total = ordered_sum(w)
    denom = jnp.maximum(w_total, 1e-9)
    return {"update_norm": ordered_sum(norms * w) / denom,
            "clip_fraction": ordered_sum(clips * w) / denom,
            "weight_total": w_total}


# ---------------------------------------------------------------------------
# Per-contribution privatization (shared by the sync chunk scan and async)
# ---------------------------------------------------------------------------
def privatize_contribution(delta, spec: AggregationSpec, rng) -> Tuple:
    """Clip one contribution (+ local noise under ``device`` placement).

    Returns (delta, pre_clip_norm, was_clipped).
    """
    delta, nrm, was_clipped = dp.clip_update(delta, spec.clip_norm)
    if spec.dev_noise > 0.0:
        delta = dp.add_noise(delta, jax.random.fold_in(rng, 1), spec.dev_noise)
    return delta, nrm, was_clipped


def accumulator_dtype(spec: AggregationSpec):
    return jnp.int32 if spec.use_secure_agg else jnp.float32


def zero_accumulator(params, spec: AggregationSpec, leading: Tuple[int, ...] = ()):
    """A zeroed aggregation accumulator shaped like ``params`` (+ leading)."""
    dt = accumulator_dtype(spec)
    return jax.tree.map(lambda x: jnp.zeros(leading + x.shape, dt), params)


def finalize_aggregate(acc, total_weight, spec: AggregationSpec, rng):
    """Decode the summed accumulator into the noised mean delta.

    ``rng`` is consumed only under ``tee`` placement: one Gaussian draw on the
    aggregate inside the trusted boundary (central DP). The TEE std is defined
    on a ``num_contributors``-sized sum, so it is rescaled by n/total_weight
    when dropout/weighting shrinks the effective aggregate.
    """
    w = jnp.maximum(total_weight, 1e-9)
    agg = decode_tree(acc, spec.sa_scale) if spec.use_secure_agg else acc
    mean = jax.tree.map(lambda a: a / w, agg)
    if spec.tee_noise > 0.0:
        mean = dp.add_noise(mean, rng, spec.tee_noise * spec.num_contributors / w)
    return mean


# ---------------------------------------------------------------------------
# Flat batched aggregation — the buffered-async hot path
# ---------------------------------------------------------------------------
def encode_and_sum_rows(buf: jnp.ndarray, weights: jnp.ndarray,
                        uniforms, noise, spec: AggregationSpec, *,
                        session: Optional[sa.MaskSession] = None,
                        use_pallas: bool = False,
                        row_sq: Optional[jnp.ndarray] = None):
    """Clip/weight/[noise]/encode[+mask] a block of rows and modular-sum it.

    The per-contribution half of ``aggregate_buffer``, factored out so a
    SHARD of a larger session can run it: the rows of ``buf`` occupy
    session slots ``session.slot_offset .. slot_offset + B - 1`` of the
    ``session.num_slots``-slot mask session (``session=None`` = unmasked).
    Because the int32 accumulation wraps mod 2^32, partial sums over
    disjoint row shards combine (``psum``) to the full buffer's accumulator
    bit-exactly — the identity the hierarchical tier is built on.

    ``uniforms`` / ``noise`` are the PRE-SLICED (B, D) blocks of the
    session-wide draws (or None), so a shard consumes exactly the rows of
    the same arrays the single-host engine would.

    ``row_sq`` (optional (B,)) supplies the per-row squared norms instead of
    computing them from ``buf`` — the pytree-native engines pass the
    whole-MODEL norms here so a chunk's rows are clipped against the global
    L2 ball even though ``buf`` holds only this chunk's columns.

    Returns (acc (D,) int32|f32, pre-clip norms (B,), was_clipped (B,)).
    """
    if session is not None and not spec.use_secure_agg:
        raise ValueError("pairwise masks require the secure-agg integer field "
                         "(spec.use_secure_agg)")
    B, D = buf.shape
    interpret = not on_tpu()
    if row_sq is not None:
        sq = row_sq
    elif use_pallas:
        from repro.kernels import dp_clip as _kclip
        pb, pd = (-B) % 8, (-D) % 512  # pad up to kernel tile multiples
        pbuf = jnp.pad(buf.astype(jnp.float32), ((0, pb), (0, pd)))
        sq = _kclip.sq_norms(pbuf, interpret=interpret)[:B]
    else:
        sq = jnp.sum(buf.astype(jnp.float32) * buf.astype(jnp.float32), axis=1)
    nrm = jnp.sqrt(sq)
    clip_scale = jnp.minimum(1.0, spec.clip_norm / jnp.maximum(nrm, 1e-12))
    was_clipped = (clip_scale < 1.0).astype(jnp.float32)

    # weighted, clipped contributions; "device" noise rides the same weights
    row_w = weights * clip_scale  # (B,)

    if spec.use_secure_agg:
        if noise is None:
            qx, qw = buf.astype(jnp.float32), row_w
        else:  # noise folded in pre-quantization; weights already applied
            qx = buf.astype(jnp.float32) * row_w[:, None] + noise
            qw = jnp.ones((B,), jnp.float32)
        if use_pallas:
            from repro.kernels import secure_agg as _ksa
            acc = _ksa.weighted_quantize_accum(
                qx, qw, uniforms, spec.sa_scale,
                session=None if session is None else _kernel_session(session),
                interpret=interpret)
        else:
            xf = qx * qw[:, None] * spec.sa_scale
            floor = jnp.floor(xf)
            bit = (uniforms < (xf - floor)).astype(jnp.float32)
            q = (floor + bit).astype(jnp.int32)
            if session is not None:
                if session.num_slots == B \
                        and isinstance(session.slot_offset, int) \
                        and session.slot_offset == 0:
                    # one deduplicated edge sweep for the whole session
                    masks = session.masks((D,))
                else:  # a shard of the session: this block's rows only
                    slots = session.slot_offset + jnp.arange(B,
                                                             dtype=jnp.int32)
                    masks = jax.vmap(
                        lambda s: session.mask((D,), s))(slots)
                q = q + masks  # wraps mod 2^32
            acc = q.sum(0)  # wraps mod 2^32
    else:
        x = buf.astype(jnp.float32) * row_w[:, None]
        if noise is not None:
            x = x + noise
        acc = x.sum(0)
    return acc, nrm, was_clipped


def _row_uniform_keys(rng, B: int):
    """Per-ROW pair keys of the batched TAG_UNIFORM stream.

    One Threefry of the row index under ``fold_in(rng, 2)`` gives every
    buffer row its own counter-based uniform stream, indexed by global flat
    element position — so a ParamPlan chunk's columns of the (B, D) uniform
    block are exactly ``stream_block(..., offset=chunk.offset)``, whatever
    the chunking.
    """
    u0, u1 = prf.key_words(jax.random.fold_in(rng, 2))
    return prf.threefry2x32(u0, u1, jnp.arange(B, dtype=prf.U32),
                            jnp.zeros((B,), prf.U32))


def buffer_noise_and_uniforms(rng, B: int, D: int, spec: AggregationSpec):
    """The session-wide stochastic draws of one buffered aggregation.

    Shared by the single-host engine and the sharded tier (which slices
    rows per leaf), so both consume bit-identical streams.  Uniforms are
    per-row counter-based PRF streams (see ``_row_uniform_keys``), so any
    column slice of the block is position-consistent.
    """
    if spec.dev_noise > 0.0:
        noise = jax.random.normal(jax.random.fold_in(rng, 1), (B, D),
                                  jnp.float32)
    else:
        noise = None
    if spec.use_secure_agg:
        r0, r1 = _row_uniform_keys(rng, B)
        uniforms = prf.bits_to_uniform(
            prf.stream_block(r0, r1, D, tag=prf.TAG_UNIFORM))
    else:
        uniforms = None
    return noise, uniforms


def aggregate_buffer(buf: jnp.ndarray, weights: jnp.ndarray,
                     spec: AggregationSpec, rng, *,
                     session: Optional[sa.MaskSession] = None,
                     use_pallas: bool = False):
    """One batched on-device aggregation of a stacked contribution buffer.

    buf:      (B, D) f32 — raw (unclipped) flattened contributions.
    weights:  (B,) f32 — per-contribution weight (staleness discount x
              validity mask); zero rows are excluded from the aggregate.
    session:  optional pairwise :class:`secure_agg.MaskSession` — every row
              gets its slot's pairwise PRF mask added to its encoded ints
              inside the fused accumulation (the in-TEE masked path).  The
              masks cancel in the modular sum, and on the Pallas path they
              are generated IN-KERNEL per VMEM tile from counters
              (``prf`` streams) — no (B, D) mask array ever exists in HBM.
              The jnp fallback materializes them via one deduplicated
              ``session.masks`` sweep.  Requires ``spec.use_secure_agg``.

    Returns (mean_delta_flat (D,), stats dict). The whole computation is
    traceable: clip scales from per-row squared norms, weighting, stochastic
    fixed-point encode, wraparound int32 sum, decode, weight-normalized mean,
    TEE noise — with an optional fused Pallas path (sq-norms kernel + fused
    weight/quantize/accumulate kernel) that never materializes the encoded
    per-contribution ints in HBM.
    """
    B, D = buf.shape
    noise, uniforms = buffer_noise_and_uniforms(rng, B, D, spec)
    if noise is not None:
        noise = noise * (spec.dev_noise * weights)[:, None]
    acc, nrm, was_clipped = encode_and_sum_rows(
        buf, weights, uniforms, noise, spec, session=session,
        use_pallas=use_pallas)

    stats = slot_metrics(weights, nrm, was_clipped)
    mean = finalize_aggregate(acc, stats["weight_total"], spec,
                              jax.random.fold_in(rng, 0xDEE))
    return mean, stats


# ---------------------------------------------------------------------------
# ParamPlan — the pytree-native chunk layout
# ---------------------------------------------------------------------------
# Chunk session keys: fold_in(fold_in(engine_key, CHUNK_SESSION_TAG), c).
# Disjoint from every other stream tag in the system (0x5E55 sync session,
# 0x7EE tee session, 0xDEE tee noise, 0xA5 push base, 0x5A5E session seed,
# 0x1EAF/0x4007 two-level leaf/root, 0x6B52 graph perm, 0xCB01 compression
# operator — compression.COMPRESSION_TAG, folded from each CHUNK session
# key by plan_operators).
CHUNK_SESSION_TAG = 0xC401

# Multi-chunk plans pad each chunk to this multiple so the fused Pallas
# kernels see tile-aligned widths (== kernels.secure_agg.DEFAULT_BLOCK_D,
# kept literal here so building a plan never imports the Pallas stack).
DEFAULT_CHUNK_BLOCK = 512


class ChunkSpec(NamedTuple):
    """One flat chunk of a :class:`ParamPlan` — consecutive WHOLE leaves.

    ``offset`` is the chunk's start in GLOBAL UNPADDED flat position — the
    index every counter-based stream (stochastic-rounding uniforms) is
    keyed by, so a chunk consumes exactly its slice of the model-wide
    stream regardless of how its storage is padded.
    """

    leaf_lo: int   # first leaf index (inclusive)
    leaf_hi: int   # last leaf index (exclusive)
    size: int      # unpadded element count (sum of member leaf sizes)
    padded: int    # storage width (kernel-block multiple; == size if 1 chunk)
    offset: int    # global unpadded flat position of the chunk start


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class ParamPlan:
    """Static layout of a model pytree over flat aggregation chunks.

    Registered as a STATIC pytree node: a plan is hashable metadata (no
    array data), so it can close over jitted steps or ride through them as
    an argument without triggering retraces beyond the first.

    The plan is the single source of truth for the pytree-native engines:
    which leaves live in which chunk (``chunks``), how each chunk's session
    key is derived from the engine session key (``session_keys``), and how
    flat chunk arrays map back to the model tree (``unchunk``).  A
    single-chunk plan is the degenerate case — unpadded, session key used
    verbatim — which is bit-for-bit the legacy flat (D,) engine.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[str, ...]
    chunks: Tuple[ChunkSpec, ...]

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)

    @property
    def total(self) -> int:
        """Unpadded model size D (sum of all leaf sizes)."""
        return sum(c.size for c in self.chunks)

    @property
    def leaf_sizes(self) -> Tuple[int, ...]:
        return tuple(math.prod(s) for s in self.shapes)

    @property
    def chunk_widths(self) -> Tuple[int, ...]:
        """Per-chunk STORAGE widths (padded)."""
        return tuple(c.padded for c in self.chunks)

    def leaves_of(self, tree) -> list:
        """Flatten ``tree`` and check it has the plan's structure."""
        leaves, treedef = jax.tree.flatten(tree)
        if treedef != self.treedef:
            raise ValueError(
                f"pytree structure does not match the ParamPlan: got "
                f"{treedef}, plan was built for {self.treedef}")
        return leaves

    def chunk_arrays(self, tree, *, leading: int = 0,
                     pad: bool = False) -> Tuple[jnp.ndarray, ...]:
        """``tree`` -> tuple of per-chunk flat f32 arrays.

        ``leading`` preserves that many leading batch axes on every leaf
        (0 = a single model delta, 1 = a stacked (K, ...) batch of deltas);
        ``pad`` zero-pads each chunk to its storage width.  No step ever
        concatenates these across chunks — that would be the (D,) buffer
        the plan exists to avoid.
        """
        leaves = self.leaves_of(tree)
        out = []
        for ck in self.chunks:
            segs = [
                leaves[i].reshape(leaves[i].shape[:leading] + (-1,))
                .astype(jnp.float32)
                for i in range(ck.leaf_lo, ck.leaf_hi)
            ]
            arr = segs[0] if len(segs) == 1 else jnp.concatenate(segs, axis=-1)
            if pad and ck.padded > ck.size:
                arr = jnp.pad(arr, [(0, 0)] * leading
                              + [(0, ck.padded - ck.size)])
            out.append(arr)
        return tuple(out)

    def unchunk(self, chunk_arrays: Sequence[jnp.ndarray]):
        """Per-chunk flat arrays (padded or not) -> the model pytree."""
        sizes = self.leaf_sizes
        leaves = []
        for ck, arr in zip(self.chunks, chunk_arrays):
            off = 0
            for i in range(ck.leaf_lo, ck.leaf_hi):
                leaves.append(arr[off:off + sizes[i]].reshape(self.shapes[i]))
                off += sizes[i]
        return jax.tree.unflatten(self.treedef, leaves)

    def session_keys(self, key) -> Tuple:
        """Per-chunk mask-session keys derived from the engine session key.

        The single-chunk plan uses the engine key VERBATIM (the legacy
        contract external reconstructions rely on); multi-chunk plans fold
        each chunk index under ``CHUNK_SESSION_TAG``.  Each chunk's session
        is a complete, independent pairwise protocol — masks cancel and
        dropout recovers per chunk, so the decoded aggregate never depends
        on the keying split.
        """
        if self.num_chunks == 1:
            return (key,)
        base = jax.random.fold_in(key, CHUNK_SESSION_TAG)
        return tuple(jax.random.fold_in(base, c)
                     for c in range(self.num_chunks))

    def chunk_noise_key(self, rng, c: int):
        """The ``fold_in(rng, 1)`` device-noise stream, per chunk.

        Single-chunk = the legacy key verbatim (bit-identical noise);
        multi-chunk folds the chunk index, so chunked device noise is a
        DIFFERENT (equal-law) draw than the flat engine's — the one
        documented non-bit-identical stream between chunkings.
        """
        k = jax.random.fold_in(rng, 1)
        return k if self.num_chunks == 1 else jax.random.fold_in(k, c)


def make_param_plan(params, *, chunk_elems: int = 0,
                    block: int = DEFAULT_CHUNK_BLOCK) -> ParamPlan:
    """Build the chunk layout of a model pytree.

    ``chunk_elems <= 0`` (the default) yields the degenerate single-chunk
    plan: one unpadded chunk spanning every leaf — the legacy flat engine.
    Otherwise leaves are grouped greedily in tree order: a chunk closes
    when admitting the next leaf would exceed ``chunk_elems`` (a leaf
    larger than ``chunk_elems`` gets a chunk of its own).  Leaves are never
    split across chunks, which is what keeps per-leaf norms, mask streams
    and dropout recovery whole-leaf-aligned.  Multi-chunk storage widths
    are padded up to ``block`` multiples for the fused kernels; padding is
    excluded from norms by construction and encodes to q == 0.
    """
    leaves, treedef = jax.tree.flatten(params)
    if not leaves:
        raise ValueError("cannot build a ParamPlan for an empty pytree")
    shapes = tuple(tuple(x.shape) for x in leaves)
    dtypes = tuple(jnp.asarray(x).dtype.name for x in leaves)
    sizes = [math.prod(s) for s in shapes]
    if chunk_elems <= 0:
        groups = [(0, len(leaves))]
    else:
        groups, lo, cur = [], 0, 0
        for i, sz in enumerate(sizes):
            if cur > 0 and cur + sz > chunk_elems:
                groups.append((lo, i))
                lo, cur = i, 0
            cur += sz
        groups.append((lo, len(leaves)))
    multi = len(groups) > 1
    chunks, off = [], 0
    for (g_lo, g_hi) in groups:
        size = sum(sizes[g_lo:g_hi])
        padded = -(-size // block) * block if multi else size
        chunks.append(ChunkSpec(g_lo, g_hi, size, padded, off))
        off += size
    return ParamPlan(treedef=treedef, shapes=shapes, dtypes=dtypes,
                     chunks=tuple(chunks))


def plan_for(params, fl_cfg) -> ParamPlan:
    """The plan an engine derives from its config — the one entry point."""
    return make_param_plan(
        params, chunk_elems=getattr(fl_cfg, "param_chunk_elems", 0))


def plan_sq_norms(plan: ParamPlan, chunk_arrays: Sequence[jnp.ndarray]):
    """Whole-model squared L2 norms from per-chunk flat arrays.

    The ``dp.global_norm`` left-fold (zero + leaf_0 + leaf_1 + ...) over
    exact leaf segments, so padding never contributes and the value is
    chunk-INVARIANT: any chunking of the same model folds the same per-leaf
    partial sums in the same order.  Arrays may carry leading batch axes
    (the last axis is the chunk's flat storage).
    """
    sizes = plan.leaf_sizes
    sq = jnp.float32(0.0)
    for ck, arr in zip(plan.chunks, chunk_arrays):
        x = arr.astype(jnp.float32)
        off = 0
        for i in range(ck.leaf_lo, ck.leaf_hi):
            seg = x[..., off:off + sizes[i]]
            sq = sq + jnp.sum(seg * seg, axis=-1)
            off += sizes[i]
    return sq


def plan_sessions(spec: AggregationSpec, plan: ParamPlan, key, *,
                  num_slots: Optional[int] = None, slot_offset=0):
    """One :class:`secure_agg.MaskSession` per chunk (or None if no key)."""
    if key is None:
        return None
    return tuple(
        make_mask_session(spec, k, num_slots=num_slots,
                          slot_offset=slot_offset)
        for k in plan.session_keys(key))


def plan_wire_chunks(spec: AggregationSpec, plan: ParamPlan):
    """Per-chunk WIRE widths under the spec's compression (identity spec =
    the plan's own widths verbatim).  Every streamed buffer, recovery
    sweep, mask and packed word count runs at these widths."""
    return comp.wire_chunks(spec.compression, plan.chunks)


def plan_operators(spec: AggregationSpec, plan: ParamPlan, session_key):
    """Per-chunk compression operators, or None for the identity spec.

    Derived from the ENGINE session key: each chunk's session key
    (``plan.session_keys``) folds :data:`compression.COMPRESSION_TAG`, so
    both ends of the push split — and both tier topologies, whose leaf
    partials all sum into one root aggregate — regenerate the SAME
    operator with no wire payload.  Deliberately slot-invariant: the
    server accumulates in the sketch domain and expands the SUM once at
    decode, which requires every contributor to share one linear operator
    per chunk (see compression.py).  When the session rolls, the key
    rolls, and so do the operators.
    """
    c = spec.compression
    if c.identity:
        return None
    return tuple(
        comp.chunk_operators(
            jax.random.fold_in(k, comp.COMPRESSION_TAG), c.mode, ck.size,
            c.rate)
        for k, ck in zip(plan.session_keys(session_key), plan.chunks))


def encode_plan_flat(xs: Sequence[jnp.ndarray], weight, slot,
                     spec: AggregationSpec, plan: ParamPlan, sessions, rng, *,
                     masked: bool = True, use_pallas: bool = False,
                     ops=None):
    """The streamed per-arrival encode on PRE-CHUNKED flat arrays.

    ``xs`` is the tuple of UNPADDED per-chunk f32 arrays of one delta (what
    ``plan.chunk_arrays`` yields).  The pipeline is the legacy
    ``encode_masked_contribution`` arithmetic lifted over chunks: one
    GLOBAL clip scale from the whole-model norm, the ``fold_in(rng, 2)``
    TAG_UNIFORM stream sliced at each chunk's global offset, and each
    chunk masked under its own session at its own slot-local stream.  The
    single-chunk plan reproduces the legacy row bit-for-bit.

    ``ops`` (from :func:`plan_operators`) switches the chunk onto the
    COMPRESSED wire: rotate/subsample in the operator domain, stochastic
    quantize there (uniform stream positions are operator-domain indices
    at the chunk's global offset), gather the kept coordinates, then mask
    at the WIRE width — masks, recovery and packing all live in the sketch
    domain from here on.

    Returns (tuple of PADDED (wire_padded_c,) int32 rows, pre-clip norm,
    was_clipped).
    """
    sq = plan_sq_norms(plan, xs)
    nrm = jnp.sqrt(sq)
    clip_scale = jnp.minimum(1.0, spec.clip_norm / jnp.maximum(nrm, 1e-12))
    weight = jnp.asarray(weight, jnp.float32)
    u_words = prf.key_words(jax.random.fold_in(rng, 2))
    wire = plan_wire_chunks(spec, plan) if ops is not None else plan.chunks
    rows = []
    for c, (ck, x) in enumerate(zip(plan.chunks, xs)):
        xw = x * (weight * clip_scale)
        if spec.dev_noise > 0.0:
            noise = jax.random.normal(plan.chunk_noise_key(rng, c), x.shape,
                                      jnp.float32)
            xw = xw + noise * (spec.dev_noise * weight)
        if ops is not None:
            op, wc = ops[c], wire[c]
            if op.mode == "sketch" and use_pallas:
                from repro.kernels import secure_agg as _ksa
                q_full = _ksa.rotate_quantize_prf(
                    xw, spec.sa_scale, op.key_words, jnp.stack(u_words),
                    u_offset=ck.offset,
                    interpret=not on_tpu())
            else:
                if op.mode == "sketch":
                    y = xw if op.full == ck.size else jnp.pad(
                        xw, (0, op.full - ck.size))
                    y = comp.block_rotate(y, op.signs)
                else:
                    y = xw
                yf = y * spec.sa_scale
                floor = jnp.floor(yf)
                bit = (prf.uniform_block(*u_words, op.full, offset=ck.offset)
                       < (yf - floor)).astype(jnp.float32)
                q_full = (floor + bit).astype(jnp.int32)
            row = jnp.take(q_full, op.idx)
            if masked:
                row = row + sessions[c].mask((wc.size,), slot)  # mod 2^32
            if wc.padded > wc.size:
                row = jnp.pad(row, (0, wc.padded - wc.size))
            rows.append(row)
            continue
        if use_pallas:
            from repro.kernels import secure_agg as _ksa
            row = _ksa.quantize_mask_prf(
                xw, spec.sa_scale, slot, jnp.stack(u_words),
                _kernel_session(sessions[c]) if masked else None,
                u_offset=ck.offset, interpret=not on_tpu())
        else:
            xf = xw * spec.sa_scale
            floor = jnp.floor(xf)
            bit = (prf.uniform_block(*u_words, ck.size, offset=ck.offset)
                   < (xf - floor)).astype(jnp.float32)
            row = (floor + bit).astype(jnp.int32)
            if masked:
                row = row + sessions[c].mask((ck.size,), slot)  # mod 2^32
        if ck.padded > ck.size:
            row = jnp.pad(row, (0, ck.padded - ck.size))
        rows.append(row)
    return tuple(rows), nrm, (clip_scale < 1.0).astype(jnp.float32)


def encode_plan_contribution(delta, weight, slot, spec: AggregationSpec,
                             plan: ParamPlan, sessions, rng, *,
                             masked: bool = True, use_pallas: bool = False,
                             ops=None):
    """Pytree form of :func:`encode_plan_flat` — the client-side encode."""
    return encode_plan_flat(plan.chunk_arrays(delta), weight, slot, spec,
                            plan, sessions, rng, masked=masked,
                            use_pallas=use_pallas, ops=ops)


def aggregate_plan_masked_buffer(bufs: Sequence[jnp.ndarray],
                                 present: jnp.ndarray, total_weight,
                                 spec: AggregationSpec, plan: ParamPlan,
                                 sessions, rng, *, recover: bool = True,
                                 masked: bool = True, ops=None):
    """Plan form of :func:`aggregate_masked_buffer`.

    ``bufs`` is the tuple of per-chunk (B, padded_c) int32 buffers; each
    chunk gates absent slots and runs ITS session's recovery sweep at the
    unpadded WIRE width (padding carries no mask shares; under an active
    compression spec the wire width is the compressed chunk size — the
    whole sweep runs in the sketch domain).  Returns the weight-normalized
    mean delta as a PYTREE shaped like the plan.
    """
    pres_i = jnp.asarray(present).astype(jnp.int32)
    wire = plan_wire_chunks(spec, plan)
    accs = []
    for c, (wc, mbuf) in enumerate(zip(wire, bufs)):
        if recover:
            acc = jnp.sum(mbuf * pres_i[:, None], axis=0)  # mod 2^32
            if masked:
                rec = sessions[c].recovery((wc.size,), present)
                if wc.padded > wc.size:
                    rec = jnp.pad(rec, (0, wc.padded - wc.size))
                acc = acc + rec
        else:
            acc = jnp.sum(mbuf, axis=0)  # full session: masks cancel exactly
        accs.append(acc)
    return finalize_plan_aggregate(accs, total_weight, spec, plan,
                                   jax.random.fold_in(rng, 0xDEE), ops=ops)


def plan_buffer_noise_and_uniforms(rng, B: int, spec: AggregationSpec,
                                   plan: ParamPlan):
    """Plan form of :func:`buffer_noise_and_uniforms` — per-chunk tuples.

    Uniforms are the SAME per-row counter streams as the flat draw, sliced
    at each chunk's global offset (bit-identical columns under any
    chunking); device noise is chunk-keyed per ``plan.chunk_noise_key``
    (single-chunk = legacy stream verbatim).  Padded tails draw uniforms
    too (the stream is position-keyed, cost-free) but zero noise.
    """
    if spec.dev_noise > 0.0:
        noise = []
        for c, ck in enumerate(plan.chunks):
            n = jax.random.normal(plan.chunk_noise_key(rng, c), (B, ck.size),
                                  jnp.float32)
            if ck.padded > ck.size:
                n = jnp.pad(n, ((0, 0), (0, ck.padded - ck.size)))
            noise.append(n)
        noise = tuple(noise)
    else:
        noise = None
    if spec.use_secure_agg:
        r0, r1 = _row_uniform_keys(rng, B)
        uniforms = tuple(
            prf.bits_to_uniform(
                prf.stream_block(r0, r1, ck.padded, tag=prf.TAG_UNIFORM,
                                 offset=ck.offset))
            for ck in plan.chunks)
    else:
        uniforms = None
    return noise, uniforms


def encode_plan_rows(bufs: Sequence[jnp.ndarray], weights: jnp.ndarray,
                     uniforms, noise, spec: AggregationSpec, plan: ParamPlan,
                     *, sessions=None, use_pallas: bool = False,
                     row_sq=None):
    """Plan form of :func:`encode_and_sum_rows` — per-chunk accumulators.

    The per-row squared norms span the WHOLE model (all chunks), so every
    chunk clips its columns by the same global scale; stats come out once.

    Returns (tuple of per-chunk accumulators, norms (B,), was_clipped (B,)).
    """
    if row_sq is None:
        row_sq = plan_sq_norms(plan, bufs)
    accs, nrm, was_clipped = [], None, None
    for c in range(plan.num_chunks):
        acc, nrm, was_clipped = encode_and_sum_rows(
            bufs[c], weights,
            None if uniforms is None else uniforms[c],
            None if noise is None else noise[c],
            spec, session=None if sessions is None else sessions[c],
            use_pallas=use_pallas, row_sq=row_sq)
        accs.append(acc)
    return tuple(accs), nrm, was_clipped


def aggregate_plan_buffer(bufs: Sequence[jnp.ndarray], weights: jnp.ndarray,
                          spec: AggregationSpec, plan: ParamPlan, rng, *,
                          sessions=None, use_pallas: bool = False):
    """Plan form of :func:`aggregate_buffer` — the batched tee/off flush.

    ``bufs`` holds per-chunk (B, padded_c) f32 raw contributions.  Masking
    (``sessions``) runs at the PADDED width per chunk: a complete batched
    session masks and sums every row, so padded-tail mask shares cancel in
    the modular sum exactly like real columns.  Returns (mean pytree,
    stats).
    """
    B = bufs[0].shape[0]
    noise, uniforms = plan_buffer_noise_and_uniforms(rng, B, spec, plan)
    if noise is not None:
        noise = tuple(n * (spec.dev_noise * weights)[:, None] for n in noise)
    accs, nrm, was_clipped = encode_plan_rows(
        bufs, weights, uniforms, noise, spec, plan, sessions=sessions,
        use_pallas=use_pallas)
    stats = slot_metrics(weights, nrm, was_clipped)
    mean = finalize_plan_aggregate(accs, stats["weight_total"], spec, plan,
                                   jax.random.fold_in(rng, 0xDEE))
    return mean, stats


def finalize_plan_aggregate(accs: Sequence[jnp.ndarray], total_weight,
                            spec: AggregationSpec, plan: ParamPlan, rng, *,
                            ops=None):
    """Plan form of :func:`finalize_aggregate`: decode, mean, TEE noise.

    Slices each chunk's padded tail, decodes, divides by the total weight,
    reassembles the MODEL PYTREE, and draws TEE noise on the tree
    (``dp.add_noise`` keys per leaf, so the draw is chunk-invariant — it
    depends only on the model structure, never on the chunking).

    ``ops`` (from :func:`plan_operators`) decodes a SKETCH-DOMAIN
    accumulator: the chunk's wire coordinates are recentered and descaled
    in the field, then expanded once — ``(full/m) · Rᵀ Sᵀ`` over the
    already-summed aggregate, the only full-width touch in the whole
    compressed pipeline.
    """
    w = jnp.maximum(total_weight, 1e-9)
    flats = []
    for c, (ck, acc) in enumerate(zip(plan.chunks, accs)):
        op = None if ops is None else ops[c]
        a = acc[:ck.size] if op is None else acc[:op.m]
        if spec.use_secure_agg:
            # the accumulator is a mod-2^32 representative of the mod-C sum
            # (C = spec.field_modulus): raw masked rows sum to the signed
            # value directly, but rows that travelled the PACKED wire enter
            # as canonical [0, C) residues, so the sum must be re-centered
            # into the wraparound window before leaving the field.  For raw
            # rows the re-center is the identity on the value (|sum| < C/2
            # by field sizing), so both ingest formats decode bit-equal.
            a = sa.recenter(a, spec.field_modulus)
            a = a.astype(jnp.float32) / spec.sa_scale
        if op is not None:
            a = comp.expand(a, op, ck.size)
        flats.append(a / w)
    mean = plan.unchunk(flats)
    if spec.tee_noise > 0.0:
        mean = dp.add_noise(mean, rng,
                            spec.tee_noise * spec.num_contributors / w)
    return mean
