"""Pallas TPU kernels: secure-aggregation fixed-point encode (+ PRF masks).

Elementwise hot loop of the TEE protocol: clip/weight, stochastic round,
cast to int32, add pairwise masks with wraparound, accumulate.  Blocked at
8x512 f32 tiles (VMEM-aligned); purely VPU work, so the roofline is
HBM-bandwidth — one read of the inputs, one int32 write.

Pairwise session masks are generated *inside* the kernels with the
counter-based PRF from ``repro.kernels.prf`` (Threefry-2x32 keyed by
``(session_key, lo_slot, hi_slot)``, indexed by flat element position): each
tile computes its own mask words from its grid offset while the data tile is
resident in VMEM.  Masks therefore never exist in HBM — the mask lane costs
zero extra HBM bandwidth and rides the same memory-bound pipeline as the
encode.  Every masked wrapper consumes the session through one
:class:`SessionMeta` lane (the kernels' view of a protocol-layer
``core.fl.secure_agg.MaskSession`` — the kernels deliberately never import
the protocol layer); on the chip that lane is one small SMEM operand read
as scalars.  ``repro.kernels.ref`` holds the bit-exact host
oracles, and ``repro.core.fl.secure_agg.session_mask`` is the
protocol-layer reference the oracles are tested against.

All wrappers pad ragged shapes up to tile multiples and slice the result
back, so real transformer parameter counts (D % block != 0) work; padded
rows are weight-gated and padded slots are excluded from the in-kernel mask
lane (``num_slots`` counts only real session positions).
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import prf

DEFAULT_BLOCK = 4096


class SessionMeta(NamedTuple):
    """The in-kernel view of one pairwise-mask session.

    The session-meta lane of every fused kernel: what actually rides the
    scalar meta operand into a Pallas body.  Built from a protocol-layer
    ``core.fl.secure_agg.MaskSession`` (the kernels deliberately do not
    import the protocol layer — this NamedTuple is the boundary type):

      key_words:   (2,) uint32 PRF key words (``prf.key_words(session.key)``)
      num_slots:   static session size
      degree:      static canonical mask-graph degree (0 = complete)
      slot_offset: first GLOBAL slot of the rows this kernel call encodes —
                   a shard of a larger session (traced ok; 0 = whole session)
      neighbors:   optional (num_slots, degree) neighbour table selecting a
                   RANDOM k-regular session graph instead of the static
                   circulant enumeration
    """

    key_words: Any
    num_slots: int
    degree: int = 0
    slot_offset: Any = 0
    neighbors: Any = None


def _kernel_name(kern) -> str:
    """The name a ``pallas_call`` carries into the compiled program and the
    profiler trace: its kernel's, ``partial(_quantize_mask_prf_kernel, ...)``
    -> ``quantize_mask_prf``."""
    fn = getattr(kern, "func", kern)
    return fn.__name__.strip("_").removesuffix("_kernel")


def _pad1(x: jnp.ndarray, mult: int) -> jnp.ndarray:
    p = (-x.shape[-1]) % mult
    return x if p == 0 else jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, p)])


def _quantize_mask_kernel(x_ref, mask_ref, u_ref, out_ref, *, scale: float,
                          value_range: float):
    x = x_ref[...].astype(jnp.float32)
    x = jnp.clip(x, -value_range, value_range) * scale
    floor = jnp.floor(x)
    bit = (u_ref[...] < (x - floor)).astype(jnp.float32)
    q = (floor + bit).astype(jnp.int32)
    out_ref[...] = q + mask_ref[...]  # int32 add wraps mod 2^32


def quantize_mask(x: jnp.ndarray, mask: jnp.ndarray, uniforms: jnp.ndarray,
                  scale: float, value_range: float, *,
                  block: int = DEFAULT_BLOCK, interpret: bool = False) -> jnp.ndarray:
    """x, uniforms: (D,) f32; mask: (D,) int32 -> masked fixed-point int32.

    Any D works: ragged tails are zero-padded to the block size and sliced
    off the output.
    """
    (D,) = x.shape
    block = min(block, D)
    x, mask, uniforms = _pad1(x, block), _pad1(mask, block), _pad1(uniforms, block)
    kern = functools.partial(_quantize_mask_kernel, scale=scale,
                             value_range=value_range)
    out = pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=(x.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))] * 3,
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0],), jnp.int32),
        interpret=interpret,
    )(x, mask, uniforms)
    return out[:D]


# ---------------------------------------------------------------------------
# Fused client push: encode + in-kernel PRF mask (+ in-kernel uniforms)
# ---------------------------------------------------------------------------
def _neighbor_list(num_slots: int, degree: int):
    """Static neighbour enumeration for the in-kernel mask lanes.

    Returns a list of callables mapping a (scalar) slot to a neighbour slot
    id — unrolled in the kernel body.  degree 0 = complete graph (gated
    diagonal); even k = ring graph ((slot +- j) % num_slots).
    """
    # same canonicalization rule as core/fl/secure_agg.effective_degree
    # (kept independent — kernels must not import the protocol layer)
    if degree <= 0 or degree >= num_slots - 1:
        return [lambda slot, d=d: jnp.int32(d) for d in range(num_slots)]
    if degree % 2 != 0:
        raise ValueError(f"ring mask-graph degree must be even, got {degree}")
    offs = [j for j in range(1, degree // 2 + 1)] \
        + [-j for j in range(1, degree // 2 + 1)]
    return [lambda slot, o=o: (slot + o + num_slots) % num_slots
            for o in offs]


def _table_neighbors(table, n_nbrs: int):
    """Neighbour callables over a RANDOM k-regular graph's table.

    The flattened (num_slots, n_nbrs) table rides its own SMEM operand
    (``table``, a :class:`_Words` view); each lookup is one scalar read at
    a traced slot.
    """
    return [lambda slot, j=j: table[slot * n_nbrs + j].astype(jnp.int32)
            for j in range(n_nbrs)]


def _session_mask(k0, k1, slot, e, neighbors) -> jnp.ndarray:
    """In-kernel pairwise mask words of ``slot`` at element positions ``e``.

    ``slot`` is a scalar (one row) or a (rows, 1) column broadcasting
    against ``e``.  Statically unrolled over the slot's mask-graph
    neighbours (callables from :func:`_neighbor_list`, or columns read
    from the random graph's table): each pair's key is one Threefry of
    (lo, hi), and its stream words are regenerated from (pair key,
    position) on the vector unit — nothing mask-shaped is read from
    memory.
    """
    mask = jnp.zeros(jnp.broadcast_shapes(jnp.shape(slot), e.shape),
                     jnp.int32)
    for nb in neighbors:
        d = nb(slot)
        lo = jnp.minimum(slot, d).astype(prf.U32)
        hi = jnp.maximum(slot, d).astype(prf.U32)
        pk0, pk1 = prf.pair_keys(k0, k1, lo, hi)
        sign = jnp.where(d == slot, 0, jnp.where(slot < d, 1, -1))
        mask = mask + sign * prf.stream_at(pk0, pk1, e)  # wraps mod 2^32
    return mask


def _smem_spec():
    """The whole (small) meta operand in SMEM: scalar reads, no tiling."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


class _Words:
    """Scalar reads of one SMEM meta operand inside a batched kernel.

    The operand is either shared by the whole batch (a ``(n,)`` vector,
    ``width`` 0) or one row per batch element (a ``(B, n)`` table passed
    flattened, so SMEM holds no padded 2-D tile); the batch element is the
    kernel's first grid axis.
    """

    def __init__(self, ref, width: int):
        self._ref = ref
        self._base = pl.program_id(0) * width if width else 0

    def __getitem__(self, k):
        return self._ref[self._base + k]


def _smem_operand(meta: jnp.ndarray):
    """(flattened operand, per-element row width or 0 if shared)."""
    if meta.ndim == 1:
        return meta, 0
    return meta.reshape(-1), meta.shape[1]


def _grid_batched(call, n_vec: int):
    """Make a batched kernel call vmappable without unrolling it.

    ``call(*vecs, *metas)`` runs ONE ``pallas_call`` whose first grid axis
    walks a batch: each of the ``n_vec`` array operands carries the batch
    as its leading axis; each SMEM meta operand is shared ``(n,)`` or one
    row per element ``(B, n)``.  Pallas's own vmap would give an SMEM
    operand a batched block, which the TPU refuses; this rule instead
    folds the vmapped axis into the batch (a vmap over a kernel that holds
    a shared meta keeps sharing it), so a vmap of any depth is still one
    kernel launch with a longer grid, and compile time does not grow with
    the batch.
    """
    f = jax.custom_batching.custom_vmap(call)

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        vb = in_batched[0]
        B = args[0].shape[1 if vb else 0]
        n = axis_size * B
        folded = []
        for k, (a, b) in enumerate(zip(args, in_batched)):
            if k < n_vec:  # (axis, B, ...) -> (axis * B, ...)
                if not b:
                    a = jnp.broadcast_to(a, (axis_size,) + a.shape)
                a = a.reshape((n,) + a.shape[2:])
            elif b and a.ndim == 2:  # a shared meta per vmapped element
                a = jnp.repeat(a, B, axis=0)
            elif b:  # per-element rows per vmapped element
                a = a.reshape(n, a.shape[-1])
            elif a.ndim == 2:  # per-element rows, the same for every vmap
                a = jnp.tile(a, (axis_size, 1))
            folded.append(a)
        out = f(*folded)
        return out.reshape((axis_size, B) + out.shape[1:]), True

    return f


ROW = 512  # lanes of one row of the 2-D (rows, ROW) element tiles


def _row_tiles(D: int, block: int):
    """Tiling of a flat (D,) vector as (rows, ROW): returns (rows per
    tile, padded row count).  ``block`` (elements per tile) rounds up to
    whole rows; a vector shorter than one tile is a single full tile."""
    rows = -(-D // ROW)
    rb = min(max(block // ROW, 1), rows)
    return rb, -(-rows // rb) * rb


def _tile_positions(rows: int) -> jnp.ndarray:
    """Flat element positions of the current (rows, ROW) tile, uint32
    (grid axis 0 walks the batch, axis 1 the tiles)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, ROW), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, ROW), 0)
    return ((pl.program_id(1) * rows + row) * ROW + lane).astype(prf.U32)


def _row_tile_call(kern, x, smem, rb: int, interpret: bool):
    """One (B, rows, ROW) -> int32 kernel over grid (batch, row tiles),
    its SMEM operands read whole."""
    B, rows, _ = x.shape
    tile = pl.BlockSpec((pl.squeezed, rb, ROW), lambda b, i: (b, i, 0))
    return pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=(B, rows // rb),
        in_specs=[tile] + [_smem_spec()] * len(smem),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((B, rows, ROW), jnp.int32),
        interpret=interpret,
    )(x, *smem)


def _quantize_mask_prf_kernel(x_ref, meta_ref, *refs, scale: float,
                              num_slots: int, degree: int, rows: int,
                              n_nbrs: int, masked: bool, meta_w: int,
                              table_w: int):
    # meta (SMEM): (6,) uint32 = uniform key words, slot id,
    # uniform-stream element offset, mask key words; with a random graph,
    # a second SMEM operand holds the flattened neighbour table
    out_ref = refs[-1]
    meta = _Words(meta_ref, meta_w)
    u0, u1 = meta[0], meta[1]
    slot = meta[2].astype(jnp.int32)
    u_off = meta[3]
    e = _tile_positions(rows)

    xf = x_ref[...].astype(jnp.float32) * scale
    floor = jnp.floor(xf)
    # the stochastic-rounding stream is indexed by GLOBAL model position
    # (u_off = this chunk's flat offset in the ParamPlan), so chunked and
    # flat encodes consume bit-identical uniforms; the mask stream stays
    # chunk-local (each chunk is its own session)
    u = prf.bits_to_uniform(
        prf.stream_at(u0, u1, u_off + e, tag=prf.TAG_UNIFORM))
    bit = (u < (xf - floor)).astype(jnp.float32)
    q = (floor + bit).astype(jnp.int32)
    if masked:
        nbs = (_table_neighbors(_Words(refs[0], table_w), n_nbrs) if n_nbrs
               else _neighbor_list(num_slots, degree))
        q = q + _session_mask(meta[4], meta[5], slot, e, nbs)
    out_ref[...] = q


def _quantize_mask_prf_call(x, meta, *table, scale, num_slots, degree, rb,
                            n_nbrs, masked, interpret):
    smem = [_smem_operand(meta)] + [_smem_operand(t) for t in table]
    kern = functools.partial(
        _quantize_mask_prf_kernel, scale=scale, num_slots=num_slots,
        degree=degree, rows=rb, n_nbrs=n_nbrs, masked=masked,
        meta_w=smem[0][1], table_w=smem[1][1] if table else 0)
    return _row_tile_call(kern, x, [a for a, _ in smem], rb, interpret)


def quantize_mask_prf(x: jnp.ndarray, scale: float, slot,
                      uniform_key_words, session: Optional[SessionMeta], *,
                      u_offset=0,
                      block: int = DEFAULT_BLOCK,
                      interpret: bool = False) -> jnp.ndarray:
    """The fused masked-push hot loop: out = q(x * scale) + mask[slot].

    x: (D,) f32 already clipped/weighted/noised (the client pipeline's
    pre-encode value); ``uniform_key_words``: (2,) uint32 PRF key of the
    stochastic-rounding stream; ``slot``: traced ABSOLUTE session position;
    ``session``: the :class:`SessionMeta` lane — session key words, size,
    graph degree and the optional random-graph neighbour table all ride
    SMEM operands into the kernel (``slot`` is absolute, so
    ``session.slot_offset`` is ignored here).  ``session=None`` is the
    UNMASKED streamed encode: the same uniforms and rounding, no mask
    lane.  ``u_offset`` (traced ok) shifts the stochastic-rounding stream
    to this chunk's GLOBAL flat offset in a multi-chunk ``ParamPlan``
    (masks stay chunk-local — each chunk is its own session).  The vector
    is tiled as (rows, ROW) rows, ``block`` elements per grid step; a vmap
    over this function runs as one kernel whose grid also walks the batch.
    Stochastic-rounding uniforms AND the slot's pairwise session mask are
    generated in-kernel from counters — neither ever exists in HBM.
    Bit-identical to the host oracle ``ref.quantize_mask_prf``.
    """
    (D,) = x.shape
    rb, rows = _row_tiles(D, block)
    xp = jnp.pad(x.astype(jnp.float32), (0, rows * ROW - D))
    masked = session is not None
    mask_words = (jnp.asarray(session.key_words, prf.U32).reshape(2)
                  if masked else jnp.zeros((2,), prf.U32))
    meta = jnp.concatenate([
        jnp.asarray(uniform_key_words, prf.U32).reshape(2),
        jnp.asarray(slot, prf.U32).reshape(1),
        jnp.asarray(u_offset, prf.U32).reshape(1),
        mask_words])
    table, n_nbrs, num_slots, degree = [], 0, 0, 0
    if masked:
        num_slots, degree = session.num_slots, session.degree
        if session.neighbors is not None:
            n_nbrs = int(session.neighbors.shape[1])
            table = [jnp.asarray(session.neighbors, prf.U32)
                     .reshape(num_slots * n_nbrs)]
    call = functools.partial(
        _quantize_mask_prf_call, scale=scale, num_slots=num_slots,
        degree=degree, rb=rb, n_nbrs=n_nbrs, masked=masked,
        interpret=interpret)
    out = _grid_batched(call, 1)(xp.reshape(1, rows, ROW), meta, *table)
    return out.reshape(rows * ROW)[:D]


# ---------------------------------------------------------------------------
# Fused compression lane: sign-flip ∘ block-FWHT rotate + stochastic quantize
# ---------------------------------------------------------------------------
SKETCH_BLOCK = ROW  # == core.fl.compression.SKETCH_BLOCK (Hadamard width)


def _rotate_quantize_prf_kernel(x_ref, meta_ref, out_ref, *, scale: float,
                                rows: int, meta_w: int):
    """``rows`` Hadamard blocks of the rotation sketch's client encode.

    The tile is (rows, ROW): one Hadamard block per row, its elements
    along the lanes.  The rotation mixes elements WITHIN a block only, so
    the grid is embarrassingly parallel over row tiles.  The ±1 diagonal
    is regenerated in-kernel from the operator key's TAG_SIGN counter
    stream (position = the element's operator-domain index), and the
    stochastic-rounding uniforms come from the TAG_UNIFORM stream at the
    chunk's global offset — the same words the uncompressed encode would
    consume.

    The butterfly computes each stage of ``core.fl.compression.fwht``'s
    reshape cascade with lane rotations: element ``i`` meets its partner
    ``i ^ h`` and keeps ``x_i + x_p`` (bit h of i clear) or ``x_p - x_i``
    (set) — the same adds in the same operand order, so the result is
    bit-identical to the host path.  The partner is taken from whichever
    of the two opposite rotations carries lane ``i ^ h``, read off a
    rotated lane iota, so the result does not depend on the rotation's
    sign convention.
    """
    import math as _math
    # meta (SMEM): (5,) uint32 = operator key words, uniform key words,
    # u offset
    meta = _Words(meta_ref, meta_w)
    o0, o1 = meta[0], meta[1]
    u0, u1 = meta[2], meta[3]
    u_off = meta[4]
    block = ROW
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    e = _tile_positions(rows)
    sbits = prf.stream_at(o0, o1, e, tag=prf.TAG_SIGN)
    signs = 1.0 - 2.0 * (sbits & 1).astype(jnp.float32)
    x = x_ref[...].astype(jnp.float32) * signs
    h = 1
    while h < block:  # static unroll: log2(block) butterfly stages
        fwd = pltpu.roll(x, h, 1)
        bwd = pltpu.roll(x, block - h, 1)
        from_fwd = pltpu.roll(lane, h, 1) == (lane ^ h)
        partner = jnp.where(from_fwd, fwd, bwd)
        x = jnp.where((lane & h) == 0, x + partner, partner - x)
        h *= 2
    x = x * jnp.float32(1.0 / _math.sqrt(block))
    xf = x * scale
    floor = jnp.floor(xf)
    u = prf.bits_to_uniform(
        prf.stream_at(u0, u1, u_off + e, tag=prf.TAG_UNIFORM))
    bit = (u < (xf - floor)).astype(jnp.float32)
    out_ref[...] = (floor + bit).astype(jnp.int32)


def rotate_quantize_prf(x: jnp.ndarray, scale: float, op_key_words,
                        uniform_key_words, *, u_offset=0,
                        block: int = DEFAULT_BLOCK,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused sketch encode: q(scale * blockFWHT(signs ⊙ x)) -> int32.

    x: (D,) f32 already clipped/weighted (the pre-encode client value);
    ``op_key_words``: (2,) uint32 words of the chunk's compression operator
    key (``fold_in(chunk_session_key, COMPRESSION_TAG)``);
    ``uniform_key_words``: (2,) uint32 stochastic-rounding PRF key;
    ``u_offset`` (traced ok) shifts the uniform stream to the chunk's
    global flat offset; a vmap runs as one kernel with a batch grid
    axis.  Returns the FULL operator-domain quantized vector
    — length ``ceil(D / SKETCH_BLOCK) * SKETCH_BLOCK``, the Hadamard pad
    included — so the caller can gather the operator's kept coordinates
    from it.  ``block`` is the elements per grid step (whole Hadamard
    blocks).  Bit-identical to the host oracle ``ref.rotate_quantize_prf``
    and to the unfused ``compression.block_rotate`` + stochastic-quantize
    path.
    """
    (D,) = x.shape
    rb, rows = _row_tiles(D, block)
    xp = jnp.pad(x.astype(jnp.float32), (0, rows * ROW - D))
    meta = jnp.concatenate([
        jnp.asarray(op_key_words, prf.U32).reshape(2),
        jnp.asarray(uniform_key_words, prf.U32).reshape(2),
        jnp.asarray(u_offset, prf.U32).reshape(1)])
    def call(x, meta):
        meta, meta_w = _smem_operand(meta)
        kern = functools.partial(_rotate_quantize_prf_kernel, scale=scale,
                                 rows=rb, meta_w=meta_w)
        return _row_tile_call(kern, x, [meta], rb, interpret)

    out = _grid_batched(call, 1)(xp.reshape(1, rows, ROW), meta)
    return out.reshape(rows * ROW)[:-(-D // ROW) * ROW]


DEFAULT_BLOCK_D = 512
DEFAULT_BLOCK_C = 8


def _encode_rows(x_ref, w_ref, u_ref, scale: float) -> jnp.ndarray:
    """The weighted stochastic fixed-point encode of one (block_c, block_d)
    tile; ``w_ref`` is the (block_c, 1) weight column."""
    x = x_ref[...].astype(jnp.float32)
    xf = x * w_ref[...].astype(jnp.float32) * scale
    floor = jnp.floor(xf)
    bit = (u_ref[...] < (xf - floor)).astype(jnp.float32)
    return (floor + bit).astype(jnp.int32)


def _weighted_quantize_accum_kernel(x_ref, w_ref, u_ref, out_ref, *,
                                    scale: float):
    i = pl.program_id(1)  # client-block index (innermost: accumulation)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = _encode_rows(x_ref, w_ref, u_ref, scale)
    out_ref[...] += jnp.sum(q, axis=0, keepdims=True)  # wraps mod 2^32


def _masked_weighted_quantize_accum_kernel(x_ref, w_ref, u_ref, m_ref,
                                           out_ref, *, scale: float):
    """The explicit-mask lane: precomputed masks ride the same fused pass."""
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    q = _encode_rows(x_ref, w_ref, u_ref, scale) + m_ref[...]  # wraps
    # masks cancel over the full session
    out_ref[...] += jnp.sum(q, axis=0, keepdims=True)


def _prf_masked_weighted_quantize_accum_kernel(
        x_ref, w_ref, u_ref, meta_ref, *refs, scale: float,
        num_slots: int, degree: int, block_c: int, block_d: int,
        valid_rows: int, n_nbrs: int, meta_w: int, table_w: int):
    """The in-kernel PRF mask lane: pairwise session masks are generated
    from counters while each (client, d) tile sits in VMEM — per-client
    encoded ints exist only as VMEM tiles with their mask already added.
    Nothing mask-shaped is ever read from or written to HBM, which is the
    in-TEE secure-aggregation property the fusion models.  Grid axis 0
    walks a batch of independent accumulations (see :func:`_grid_batched`).
    """
    out_ref = refs[-1]
    j = pl.program_id(1)  # d-block index
    i = pl.program_id(2)  # client-block index (innermost: accumulation)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # meta (SMEM): (3,) uint32 = session key words, the shard's first
    # global slot; with a random graph, a second SMEM operand holds the
    # flattened neighbour table
    meta = _Words(meta_ref, meta_w)
    k0, k1 = meta[0], meta[1]
    offset = meta[2].astype(jnp.int32)
    q = _encode_rows(x_ref, w_ref, u_ref, scale)

    row = jax.lax.broadcasted_iota(jnp.int32, (block_c, 1), 0)
    local = i * block_c + row  # row index within this shard
    rows = offset + local  # global session slots of this client block
    if n_nbrs:
        table = _Words(refs[0], table_w)

        # neighbour j of every row, read from the SMEM table row by row
        # (at a clamped slot, so the reads stay inside the table)
        def column(j_):
            col = jnp.zeros((block_c, 1), jnp.int32)
            for r in range(block_c):
                s_r = jnp.minimum(offset + i * block_c + r, num_slots - 1)
                col = jnp.where(row == r, table[s_r * n_nbrs + j_]
                                .astype(jnp.int32), col)
            return col

        nbs = [lambda _, j_=j_: column(j_) for j_ in range(n_nbrs)]
    else:
        nbs = _neighbor_list(num_slots, degree)
    e = (j * block_d + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_d), 1)).astype(prf.U32)
    mask = _session_mask(k0, k1, rows, e, nbs)
    # padded client rows (local >= valid_rows) and rows beyond the session
    # (global slot >= num_slots) are not session members: their masks would
    # not cancel, so the lane gates them to zero (their weight is already
    # zero, so q is zero too)
    mask = jnp.where((local < valid_rows) & (rows < num_slots), mask, 0)
    out_ref[...] += jnp.sum(q + mask, axis=0, keepdims=True)  # mod 2^32


def _session_accum_call(x, weights, uniforms, meta, *table, scale,
                        num_slots, degree, block_c, block_d, valid_rows,
                        n_nbrs, interpret):
    """The session lane over a batch: (B, Cp, Dp) -> (B, 1, Dp) int32."""
    B, Cp, Dp = x.shape
    smem = [_smem_operand(meta)] + [_smem_operand(t) for t in table]
    kern = functools.partial(
        _prf_masked_weighted_quantize_accum_kernel, scale=scale,
        num_slots=num_slots, degree=degree, block_c=block_c,
        block_d=block_d, valid_rows=valid_rows, n_nbrs=n_nbrs,
        meta_w=smem[0][1], table_w=smem[1][1] if table else 0)
    sq = pl.squeezed
    cd_spec = pl.BlockSpec((sq, block_c, block_d), lambda b, j, i: (b, i, j))
    c_spec = pl.BlockSpec((sq, block_c, 1), lambda b, j, i: (b, i, 0))
    return pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=(B, Dp // block_d, Cp // block_c),
        in_specs=[cd_spec, c_spec, cd_spec] + [_smem_spec()] * len(smem),
        out_specs=pl.BlockSpec((sq, 1, block_d), lambda b, j, i: (b, 0, j)),
        out_shape=jax.ShapeDtypeStruct((B, 1, Dp), jnp.int32),
        interpret=interpret,
    )(x, weights, uniforms, *[a for a, _ in smem])


def weighted_quantize_accum(x: jnp.ndarray, weights: jnp.ndarray,
                            uniforms: jnp.ndarray, scale: float, *,
                            masks: jnp.ndarray = None,
                            session: SessionMeta = None,
                            block_c: int = DEFAULT_BLOCK_C,
                            block_d: int = DEFAULT_BLOCK_D,
                            interpret: bool = False) -> jnp.ndarray:
    """Fused buffered-async hot loop: out[d] = sum_c [q(w[c] * x[c, d]) + m].

    x, uniforms: (C, D) f32; weights: (C,) f32 -> (D,) int32 wraparound sum.
    Each contribution is weighted, stochastic-round fixed-point encoded,
    optionally pairwise-masked and accumulated in one pass — the encoded
    per-client ints never touch HBM.  Over a full session the masks sum to
    zero mod 2^32, so the masked output is bit-identical to the unmasked one.

    Mask lanes (mutually exclusive):
      masks   — precomputed (C, D) int32 masks read from HBM (the PR 2
                path, kept for the explicit-mask oracle tests);
      session — the :class:`SessionMeta` lane: masks are generated
                IN-KERNEL per tile from the session's (2,)-word PRF key (no
                HBM mask traffic at all).  ``session.num_slots`` bounds the
                session; slots beyond it (padding) are excluded from the
                lane.  ``session.degree`` selects the mask graph
                (0 = complete), ``session.neighbors`` an optional random
                k-regular table, and ``session.slot_offset`` (traced ok)
                places row c at global session slot ``slot_offset + c`` —
                the hierarchy tier's per-leaf shard of one large session.
                Its meta rides SMEM, and a vmap runs as one kernel with a
                batch grid axis (:func:`_grid_batched`); the other lanes
                use Pallas's own vmap, which does the same.

    Ragged C or D are padded up to tile multiples (padded rows carry zero
    weight) and the output is sliced back to (D,).  Weights travel as a
    (C, 1) column and the sum as a (1, D) row, so every block is a 2-D
    tile the chip's layouts agree on.
    """
    if masks is not None and session is not None:
        raise ValueError("pass either precomputed `masks` or a PRF "
                         "`session` meta, not both")
    C, D = x.shape
    block_c = min(block_c, C)
    block_d = min(block_d, D)
    pc, pd = (-C) % block_c, (-D) % block_d
    x = jnp.pad(x.astype(jnp.float32), ((0, pc), (0, pd)))
    uniforms = jnp.pad(uniforms, ((0, pc), (0, pd)))
    weights = jnp.pad(weights.astype(jnp.float32), (0, pc)).reshape(-1, 1)
    Cp, Dp = x.shape

    if session is not None:
        num_slots, neighbors = session.num_slots, session.neighbors
        n_nbrs = 0 if neighbors is None else int(neighbors.shape[1])
        meta = jnp.concatenate([
            jnp.asarray(session.key_words, prf.U32).reshape(2),
            jnp.asarray(session.slot_offset, prf.U32).reshape(1)])
        table = [] if neighbors is None else [
            jnp.asarray(neighbors, prf.U32).reshape(num_slots * n_nbrs)]
        call = functools.partial(
            _session_accum_call, scale=scale, num_slots=num_slots,
            degree=session.degree, block_c=block_c, block_d=block_d,
            valid_rows=C, n_nbrs=n_nbrs, interpret=interpret)
        out = _grid_batched(call, 3)(x[None], weights[None], uniforms[None],
                                     meta, *table)
        return out[0, 0, :D]

    grid = (Dp // block_d, Cp // block_c)  # clients innermost for accumulation
    cd_spec = pl.BlockSpec((block_c, block_d), lambda j, i: (i, j))
    c_spec = pl.BlockSpec((block_c, 1), lambda j, i: (i, 0))
    if masks is not None:
        kern = functools.partial(_masked_weighted_quantize_accum_kernel,
                                 scale=scale)
        in_specs = [cd_spec, c_spec, cd_spec, cd_spec]
        args = (x, weights, uniforms, jnp.pad(masks, ((0, pc), (0, pd))))
    else:
        kern = functools.partial(_weighted_quantize_accum_kernel, scale=scale)
        in_specs, args = [cd_spec, c_spec, cd_spec], (x, weights, uniforms)
    out = pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_d), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.int32),
        interpret=interpret,
    )(*args)
    return out[0, :D]


# ---------------------------------------------------------------------------
# Wire codec: bit-pack canonical field residues into dense uint32 words
# ---------------------------------------------------------------------------
DEFAULT_BLOCK_G = 256  # 32-element residue groups per tile (8192 elements)
LANES = 128  # groups side by side along the lanes of one tile row


def _group_tiles(size: int, block_g: int):
    """Tiling of ``size`` residues into 32-element groups laid 128 to a
    row: returns (rows per tile, padded row count).  ``block_g`` (groups
    per tile) rounds up to whole rows of 128 groups."""
    rows = -(-size // (32 * LANES))
    rb = min(-(-block_g // LANES), rows)
    return rb, -(-rows // rb) * rb


def _pack_residues_kernel(v_ref, out_ref, *, bits: int):
    """(rb, 32, 128) residue groups -> (rb, bits, 128) packed words.

    Group ``g`` of a tile row is lane ``g``: its 32 residues run down the
    sublanes of the input and its ``bits`` words down the sublanes of the
    output (32 consecutive ``bits``-bit residues fill exactly ``bits``
    uint32 words — their LCM alignment).  Every shift and word index
    below is STATIC: element ``j`` of a group starts at stream bit
    ``j*bits``, i.e. word ``(j*bits)//32`` at shift ``(j*bits)%32``,
    straddling into the next word when the shift crosses the 32-bit
    boundary.  Layout matches the host codec (little-endian within the
    dense bit stream).
    """
    mask = jnp.uint32((1 << bits) - 1)
    cols = [None] * bits
    for j in range(32):  # static: each element lands in <= 2 words
        v = v_ref[:, j, :].astype(jnp.uint32) & mask
        w0, shift = divmod(j * bits, 32)
        part = v << shift
        cols[w0] = part if cols[w0] is None else cols[w0] | part
        if shift + bits > 32:
            cols[w0 + 1] = v >> (32 - shift)
    for k in range(bits):
        out_ref[:, k, :] = cols[k]


def pack_residues(q: jnp.ndarray, bits: int, *,
                  block_g: int = DEFAULT_BLOCK_G,
                  interpret: bool = False) -> jnp.ndarray:
    """(D,) int32 canonical residues -> (ceil(D*bits/32),) uint32 words.

    The Pallas side of ``core.fl.secure_agg.pack_residues`` (which takes
    the field modulus; the kernels take the raw residue width so they
    never import the protocol layer).  Ragged D pads to whole rows of 128
    groups with zero residues — their bits vanish and the word stream is
    sliced back to the exact length.  The groups are turned lane-major
    (one group per lane) on the way in and back on the way out, so every
    tile is lane-dense whatever ``bits`` is.
    """
    (D,) = q.shape
    nwords = -(-D * bits // 32)
    rb, rows = _group_tiles(D, block_g)
    v = jnp.pad(q, (0, rows * LANES * 32 - D))
    v = v.reshape(rows, LANES, 32).transpose(0, 2, 1)
    kern = functools.partial(_pack_residues_kernel, bits=bits)
    out = pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, 32, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((rb, bits, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, bits, LANES), jnp.uint32),
        interpret=interpret,
    )(v)
    return out.transpose(0, 2, 1).reshape(rows * LANES * bits)[:nwords]


def _unpack_residues_kernel(w_ref, out_ref, *, bits: int):
    """(rb, bits, 128) packed words -> (rb, 32, 128) int32 residue groups."""
    mask = jnp.uint32((1 << bits) - 1)
    for j in range(32):  # static: each element reads <= 2 words
        w0, shift = divmod(j * bits, 32)
        v = w_ref[:, w0, :] >> shift
        if shift + bits > 32:
            v = v | (w_ref[:, w0 + 1, :] << (32 - shift))
        out_ref[:, j, :] = (v & mask).astype(jnp.int32)


def unpack_residues(words: jnp.ndarray, size: int, bits: int, *,
                    block_g: int = DEFAULT_BLOCK_G,
                    interpret: bool = False) -> jnp.ndarray:
    """Inverse of :func:`pack_residues`: uint32 words -> int32 residues.

    The word stream is turned lane-major with ``bits`` strided slices (word
    ``k`` of every group of a row): a reshape of the stream to a
    ``bits``-wide minor axis would make XLA relayout into (8, 128) tiles
    that are mostly padding, and at model widths it compiles for minutes.
    """
    (nwords,) = words.shape
    expect = -(-size * bits // 32)
    if nwords != expect:
        raise ValueError(f"packed stream of {nwords} words does not match "
                         f"{size} residues at {bits}-bit width "
                         f"(expected {expect})")
    rb, rows = _group_tiles(size, block_g)
    wp = jnp.pad(words, (0, rows * LANES * bits - nwords))
    wp = wp.reshape(rows, LANES * bits)
    wp = jnp.stack([wp[:, k::bits] for k in range(bits)], axis=1)
    kern = functools.partial(_unpack_residues_kernel, bits=bits)
    out = pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=(rows // rb,),
        in_specs=[pl.BlockSpec((rb, bits, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((rb, 32, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, 32, LANES), jnp.int32),
        interpret=interpret,
    )(wp)
    return out.transpose(0, 2, 1).reshape(rows * LANES * 32)[:size]


def _dequantize_kernel(q_ref, out_ref, *, inv_scale: float):
    out_ref[...] = q_ref[...].astype(jnp.float32) * inv_scale


def dequantize(q: jnp.ndarray, scale: float, *, block: int = DEFAULT_BLOCK,
               interpret: bool = False) -> jnp.ndarray:
    (D,) = q.shape
    block = min(block, D)
    qp = _pad1(q, block)
    kern = functools.partial(_dequantize_kernel, inv_scale=1.0 / scale)
    out = pl.pallas_call(
        kern,
        name=_kernel_name(kern),
        grid=(qp.shape[0] // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0],), jnp.float32),
        interpret=interpret,
    )(qp)
    return out[:D]
