"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bitagg as kbit
from repro.kernels import dp_clip as kclip
from repro.kernels import flash_decode as kflash
from repro.kernels import ref
from repro.kernels import secure_agg as ksa


@pytest.mark.parametrize("C,D", [(4, 512), (8, 1024), (16, 4096), (32, 512),
                                 (8, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sq_norms_sweep(C, D, dtype):
    key = jax.random.PRNGKey(C * D)
    x = jax.random.normal(key, (C, D)).astype(dtype)
    got = kclip.sq_norms(x, interpret=True)
    want = ref.sq_norms(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2
                               if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("C,D", [(4, 512), (16, 4096), (8, 1536)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scale_accum_sweep(C, D, dtype):
    key = jax.random.PRNGKey(C + D)
    x = jax.random.normal(key, (C, D)).astype(dtype)
    s = jax.random.uniform(jax.random.fold_in(key, 1), (C,))
    got = kclip.scale_accum(x, s, interpret=True)
    want = ref.clip_scale_accumulate(x, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("C,D,clip", [(8, 1024, 0.5), (16, 512, 2.0),
                                      (4, 4096, 0.1)])
def test_dp_clip_reduce_fused(C, D, clip):
    key = jax.random.PRNGKey(int(clip * 100))
    x = jax.random.normal(key, (C, D)) * 0.5
    got = kclip.dp_clip_reduce(x, clip, interpret=True)
    want = ref.dp_clip_reduce(x, clip)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("D", [4096, 8192, 1024])
@pytest.mark.parametrize("bits_scale", [(1 << 20, 4.0), (1000.0, 1.0)])
def test_secure_agg_encode_sweep(D, bits_scale):
    scale, vr = bits_scale
    key = jax.random.PRNGKey(D)
    x = jax.random.normal(key, (D,)) * vr
    mask = jax.random.randint(jax.random.fold_in(key, 1), (D,),
                              -2 ** 31, 2 ** 31 - 1, jnp.int32)
    u = jax.random.uniform(jax.random.fold_in(key, 2), (D,))
    got = ksa.quantize_mask(x, mask, u, scale, vr, interpret=True)
    want = ref.quantize_mask(x, mask, scale, u, value_range=vr)
    assert bool(jnp.all(got == want))  # integer path: bit-exact
    back = ksa.dequantize(got - mask, scale, interpret=True)
    np.testing.assert_allclose(np.asarray(back),
                               np.asarray(jnp.clip(x, -vr, vr)),
                               atol=1.5 / scale)


@pytest.mark.parametrize("C,D", [(8, 512), (16, 1024), (8, 2048)])
def test_weighted_quantize_accum_sweep(C, D):
    """Fused async-buffer kernel vs oracle: weight+encode+wraparound sum."""
    key = jax.random.PRNGKey(C * D + 1)
    x = jax.random.normal(key, (C, D))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (C,))
    u = jax.random.uniform(jax.random.fold_in(key, 2), (C, D))
    scale = float(1 << 20)  # f32-exact quantization grid for |x*w| <~ 4
    got = ksa.weighted_quantize_accum(x, w, u, scale, interpret=True)
    want = ref.weighted_quantize_accum(x, w, u, scale)
    assert got.dtype == jnp.int32
    assert bool(jnp.all(got == want))  # integer path: bit-exact
    back = np.asarray(ksa.dequantize(got, scale, interpret=True))
    direct = np.asarray((x * w[:, None]).sum(0))
    np.testing.assert_allclose(back, direct, atol=1.5 * C / scale)


@pytest.mark.parametrize("C,D", [(8, 512), (16, 1024)])
def test_masked_weighted_quantize_accum_sweep(C, D):
    """The mask-add lane vs oracle: weight+encode+mask+wraparound sum."""
    key = jax.random.PRNGKey(C + D + 3)
    x = jax.random.normal(key, (C, D))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (C,))
    u = jax.random.uniform(jax.random.fold_in(key, 2), (C, D))
    masks = jax.random.randint(jax.random.fold_in(key, 3), (C, D),
                               -2 ** 31, 2 ** 31 - 1, jnp.int32)
    scale = float(1 << 20)
    got = ksa.weighted_quantize_accum(x, w, u, scale, masks=masks,
                                      interpret=True)
    want = ref.weighted_quantize_accum(x, w, u, scale, masks=masks)
    assert got.dtype == jnp.int32
    assert bool(jnp.all(got == want))  # integer path: bit-exact


def test_masked_kernel_session_masks_cancel_bit_exact():
    """With a full pairwise session in the mask lane, the fused masked
    accumulation equals the unmasked kernel output bit-for-bit."""
    from repro.core.fl import secure_agg as sa
    C, D = 8, 512
    key = jax.random.PRNGKey(77)
    x = jax.random.normal(key, (C, D))
    w = jax.random.uniform(jax.random.fold_in(key, 1), (C,))
    u = jax.random.uniform(jax.random.fold_in(key, 2), (C, D))
    skey = jax.random.fold_in(key, 3)
    masks = jnp.stack([sa.session_mask((D,), s, C, skey) for s in range(C)])
    assert not bool(jnp.all(masks == 0))
    scale = float(1 << 20)
    masked = ksa.weighted_quantize_accum(x, w, u, scale, masks=masks,
                                         interpret=True)
    plain = ksa.weighted_quantize_accum(x, w, u, scale, interpret=True)
    assert bool(jnp.all(masked == plain))


def test_weighted_quantize_accum_zero_weight_rows():
    """Zero-weight (invalid/padded) slots contribute exactly nothing."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (8, 512)) * 100.0  # huge values, masked out
    u = jax.random.uniform(jax.random.fold_in(key, 1), (8, 512))
    w = jnp.zeros((8,)).at[0].set(1.0)
    got = ksa.weighted_quantize_accum(x, w, u, 1024.0, interpret=True)
    want = ref.weighted_quantize_accum(x[:1], w[:1], u[:1], 1024.0)
    assert bool(jnp.all(got == want))


@pytest.mark.parametrize("N,F,T", [(128, 8, 16), (256, 16, 8), (512, 8, 4)])
@pytest.mark.parametrize("flip", [0.0, 0.25])
def test_bitagg_sweep(N, F, T, flip):
    key = jax.random.PRNGKey(N + F + T)
    vals = jax.random.normal(key, (N, F))
    thr = jnp.linspace(-2, 2, T)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (N, F, T))
    got = kbit.bit_counts(vals, thr, u, flip, interpret=True)
    want = ref.bit_counts(vals, thr, u, flip)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)


@pytest.mark.parametrize("B,H,KV,hd,W", [(2, 8, 2, 64, 512), (1, 4, 4, 128, 256),
                                         (2, 16, 8, 64, 1024), (1, 10, 1, 256, 512)])
@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("fill", [0.4, 1.0])
def test_flash_decode_sweep(B, H, KV, hd, W, window, fill):
    key = jax.random.PRNGKey(B * H + W + window)
    q = jax.random.normal(key, (B, H, hd)) * (hd ** -0.5)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, W, KV, hd))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, W, KV, hd))
    n_valid = int(W * fill)
    slot = jnp.where(jnp.arange(W) < n_valid, jnp.arange(W), -1)
    pos = jnp.int32(n_valid - 1)
    got = kflash.flash_decode(q, k, v, slot, pos,
                              window=window, interpret=True)
    want = jnp.stack([
        ref.flash_decode(q[b], k[b], v[b], slot, pos,
                         window if window else None) for b in range(B)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_bf16():
    key = jax.random.PRNGKey(9)
    B, H, KV, hd, W = 2, 4, 2, 128, 512
    q = (jax.random.normal(key, (B, H, hd)) * (hd ** -0.5)).astype(jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, W, KV, hd)).astype(jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, W, KV, hd)).astype(jnp.bfloat16)
    slot = jnp.arange(W)
    got = kflash.flash_decode(q, k, v, slot, jnp.int32(W - 1), interpret=True)
    want = jnp.stack([ref.flash_decode(q[b], k[b], v[b], slot,
                                       jnp.int32(W - 1), None) for b in range(B)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=0.03)


# --- packed wire residues -----------------------------------------------------
@pytest.mark.parametrize("bits", [1, 7, 16, 19, 31, 32])
@pytest.mark.parametrize("D", [1, 33, 700])
def test_pack_residues_kernel_oracle_host_three_way(bits, D):
    """Kernel == bit-by-bit oracle == host protocol codec, bit for bit.

    Three independent formulations of the wire layout (group algorithm in
    the kernel, stream-bit assembly in the oracle, vectorized group
    algorithm in ``core.fl.secure_agg``) agreeing on random residues is
    the layout's correctness argument."""
    from repro.core.fl import secure_agg as fsa

    modulus = (1 << bits) if bits < 32 else (1 << 32)
    rs = np.random.RandomState(bits * 1009 + D)
    raw = jnp.asarray(
        rs.randint(-2 ** 31, 2 ** 31, size=D, dtype=np.int64).astype(np.int32))
    canon = fsa.to_field(raw, modulus)
    got = ksa.pack_residues(canon, bits, interpret=True)
    want = ref.pack_residues(canon, bits)
    host = fsa.pack_residues(canon, modulus)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(host))
    back_k = ksa.unpack_residues(got, D, bits, interpret=True)
    back_r = ref.unpack_residues(want, D, bits)
    back_h = fsa.unpack_residues(host, D, modulus)
    for back in (back_k, back_r, back_h):  # to_field output is canonical
        np.testing.assert_array_equal(np.asarray(back), np.asarray(canon))


def test_pack_residues_kernel_multi_block_grid():
    """Sizes past one grid block exercise the block-index BlockSpec path."""
    bits, D = 19, 32 * 300 + 7  # > DEFAULT_BLOCK_G groups, ragged tail
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randint(0, 1 << bits, size=D).astype(np.int32))
    got = ksa.pack_residues(q, bits, block_g=128, interpret=True)
    want = ref.pack_residues(q, bits)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    back = ksa.unpack_residues(got, D, bits, block_g=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


def test_unpack_residues_kernel_word_count_mismatch_raises():
    words = jnp.zeros((10,), jnp.uint32)
    with pytest.raises(ValueError, match="packed stream"):
        ksa.unpack_residues(words, 999, 19, interpret=True)


# --- fused sketch rotate + quantize ------------------------------------------
@pytest.mark.parametrize("D,off", [(512, 0), (700, 0), (1300, 1000),
                                   (45, 2245)])
def test_rotate_quantize_prf_kernel_oracle_host_three_way(D, off):
    """Fused sign-flip ∘ block-FWHT ∘ stochastic-round kernel == gather-
    based oracle == the host compression path, bit for bit.

    Three independent formulations of the rotation (in-kernel reshape
    butterfly, index-gather butterfly in the oracle, the reshape cascade
    in ``core.fl.compression``) plus two PRF stream forms (``stream_at``
    in the kernels, ``stream_block`` on the host) must agree exactly —
    this is what lets the Pallas lane drop into ``encode_plan_flat``
    without breaking the client/server bit-parity contract."""
    from repro.core.fl import compression as comp
    from repro.kernels import prf

    scale = float(1 << 16)
    key = jax.random.PRNGKey(D + off)
    x = jax.random.normal(key, (D,)) * 2.0
    op_key = jax.random.fold_in(key, comp.COMPRESSION_TAG)
    u_key = jax.random.fold_in(key, 0xA5)
    ow = jnp.stack(prf.key_words(op_key))
    uw = jnp.stack(prf.key_words(u_key))
    got = ksa.rotate_quantize_prf(x, scale, ow, uw, u_offset=off,
                                  interpret=True)
    want = ref.rotate_quantize_prf(x, scale, ow, uw, u_offset=off)
    assert got.dtype == want.dtype == jnp.int32
    assert bool(jnp.all(got == want))  # integer path: bit-exact
    # host path: rotate via compression.block_rotate, same uniform stream
    op = comp.chunk_operators(op_key, "sketch", D, 1.0)
    full = op.full
    y = comp.block_rotate(jnp.pad(x, (0, full - D)), op.signs) * scale
    floor = jnp.floor(y)
    u = prf.uniform_block(*prf.key_words(u_key), full, offset=off)
    host = (floor + (u < (y - floor)).astype(jnp.float32)).astype(jnp.int32)
    assert bool(jnp.all(got == host))


# --- a vmap over an SMEM-meta kernel folds into its batch grid axis ---------
_NB = jnp.asarray([[1, 2], [0, 3], [3, 0], [2, 1], [5, 4], [4, 5]], jnp.int32)


def _qmp(x, uk, slot, mk, nbrs):
    sess = ksa.SessionMeta(key_words=mk, num_slots=6, degree=2,
                           neighbors=nbrs)
    return ksa.quantize_mask_prf(x, 1e3, slot, uk, sess, u_offset=17,
                                 block=1024, interpret=True)


def _accum(x, w, u, mk, off):
    sess = ksa.SessionMeta(key_words=mk, num_slots=10, degree=2,
                           slot_offset=off)
    return ksa.weighted_quantize_accum(x, w, u, 1e3, session=sess,
                                       interpret=True)


def _vmap_cases():
    """name -> (function, in_axes per nesting level (outer first), args)."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (2, 3, 2000))
    uk = jax.random.randint(key, (2, 3, 2), 0, 2**31 - 1).astype(jnp.uint32)
    mk = jnp.asarray([[5, 7], [9, 11]], jnp.uint32)
    slots = jnp.arange(6, dtype=jnp.int32).reshape(2, 3)
    xa = jax.random.normal(key, (2, 5, 2000))
    ua = jax.random.uniform(key, (2, 5, 2000))
    return {
        # clients x rows, every scalar per element, a shared graph table
        "nested_random_graph": (
            _qmp, [(0, 0, 0, None, None), (0, 0, 0, None, None)],
            (x, uk, slots, mk[0], _NB)),
        # per-leaf sessions: the key varies over the outer axis only
        "per_leaf_session_keys": (
            _qmp, [(0, 0, 0, 0, None), (0, 0, 0, None, None)],
            (x, uk, slots, mk, _NB)),
        # a meta batched inside, shared across the outer axis
        "inner_only_meta": (
            _qmp, [(0, None, None, None, None), (0, 0, 0, None, None)],
            (x, uk[0], slots[0], mk[0], _NB)),
        # a meta shared inside, batched across the outer axis
        "outer_only_meta": (
            _qmp, [(0, 0, 0, None, None), (0, None, None, None, None)],
            (x, uk[:, 0], slots[:, 0], mk[0], _NB)),
        "rotate": (
            lambda x, uk: ksa.rotate_quantize_prf(
                x, 1e3, jnp.asarray([9, 11], jnp.uint32), uk, u_offset=5,
                block=1024, interpret=True),
            [(0, 0), (0, 0)], (x, uk)),
        "accum_session_shards": (
            _accum, [(None, None, None, None, 0), (0, 0, 0, 0, None)],
            (xa, jnp.ones((2, 5)), ua, mk, jnp.asarray([0, 5], jnp.int32))),
    }


@pytest.mark.parametrize("name", sorted(_vmap_cases()))
def test_smem_kernels_vmap_equals_per_element_calls(name):
    fn, (outer, inner), args = _vmap_cases()[name]
    got = np.asarray(jax.jit(jax.vmap(jax.vmap(fn, in_axes=inner),
                                      in_axes=outer))(*args))

    def pick(a, ax, i):
        return a if ax is None else a[i]

    for i in range(got.shape[0]):
        a1 = [pick(a, ax, i) for a, ax in zip(args, outer)]
        for j in range(got.shape[1]):
            a2 = [pick(a, ax, j) for a, ax in zip(a1, inner)]
            np.testing.assert_array_equal(got[i, j], np.asarray(fn(*a2)))
