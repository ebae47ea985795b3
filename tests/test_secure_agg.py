"""Secure aggregation: mask cancellation is EXACT; quantization is bounded."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.fl import secure_agg as sa


@settings(deadline=None, max_examples=20)
@given(st.integers(2, 12), st.integers(0, 2 ** 31 - 1))
def test_pairwise_masks_cancel_exactly(n_clients, seed):
    shape = (33,)
    peer_ids = list(range(n_clients))
    total = jnp.zeros(shape, jnp.int32)
    for c in peer_ids:
        total = total + sa.pairwise_mask(shape, c, peer_ids, seed)
    assert bool(jnp.all(total == 0))


def test_masked_sum_equals_plain_sum():
    """The server learns the sum and nothing else changes it."""
    key = jax.random.PRNGKey(0)
    n, d = 6, 257
    updates = [0.5 * jax.random.normal(jax.random.fold_in(key, i), (d,))
               for i in range(n)]
    qs = [sa.quantize(u, 32, 4.0) for u in updates]
    plain = qs[0]
    for q in qs[1:]:
        plain = plain + q
    masked = [sa.mask_update(q, c, list(range(n)), seed=7)
              for c, q in enumerate(qs)]
    agg = sa.aggregate_masked(masked)
    assert bool(jnp.all(agg == plain))  # bit-exact
    # an individual masked update looks nothing like its plaintext
    assert float(jnp.mean((masked[0] == qs[0]).astype(jnp.float32))) < 0.01


def test_full_protocol_accuracy():
    key = jax.random.PRNGKey(1)
    n, d = 8, 1024
    updates = [0.3 * jax.random.normal(jax.random.fold_in(key, i), (d,))
               for i in range(n)]
    mean = sa.secure_aggregate(updates, bits=32, value_range=4.0, seed=3)
    want = sum(updates) / n
    assert float(jnp.abs(mean - want).max()) < 1e-5


@settings(deadline=None, max_examples=25)
@given(st.integers(8, 24), st.floats(0.5, 16.0), st.integers(0, 2 ** 31 - 1))
def test_quantization_error_bound(bits, value_range, seed):
    """|dequant(quant(x)) - x| <= range/levels (round-to-nearest: half that).

    bits capped at 24: beyond the f32 mantissa the scale multiply itself
    dominates the quantization step and the bound is float-precision-limited.
    """
    key = jax.random.PRNGKey(seed)
    x = jax.random.uniform(key, (500,), minval=-value_range, maxval=value_range)
    q = sa.quantize(x, bits, value_range)
    back = sa.dequantize(q, bits, value_range)
    lsb = value_range / (2 ** (bits - 1) - 1)
    assert float(jnp.abs(back - x).max()) <= lsb * 0.5 + value_range * 1e-6


def test_stochastic_rounding_unbiased():
    key = jax.random.PRNGKey(2)
    x = jnp.full((20_000,), 0.1234567)
    q = sa.quantize(x, 8, 1.0, rng=key)  # coarse: 127 levels
    back = sa.dequantize(q, 8, 1.0)
    assert float(back.mean()) == pytest.approx(0.1234567, abs=2e-4)


# --- wraparound-window decode (the `count` parameter) ------------------------
def test_dequantize_count_recenters_wrapped_sum():
    """Regression: an int32-wrapping reduced-field sum round-trips exactly.

    4096 contributors, 16-bit values, wire residues in [0, C) with
    C = field_modulus(16, 4096) = 2^28: the int32 accumulation wraps mod 2^32
    many times, yet dequantize(count=4096) recovers the exact sum because C
    divides 2^32.  (The seed bug: `count` was accepted and silently ignored.)
    """
    bits, count = 16, 4096
    C = sa.field_modulus(bits, count)
    assert C == 1 << 28 and (1 << 32) % C == 0
    rs = np.random.RandomState(0)
    vals = rs.randint(-20_000, 20_000, size=(count, 16)).astype(np.int32)
    wire = np.asarray(sa.to_field(jnp.asarray(vals), C))
    assert wire.min() >= 0 and wire.max() < C
    acc = np.zeros(16, np.int32)
    for row in wire:
        acc = (acc + row).astype(np.int32)  # plain int32 wraparound adds
    true = vals.sum(0)
    assert np.any(acc != true), "test must actually overflow int32"
    assert np.any(np.abs(true) > 1 << 16), "sums must exceed the 1-count window"
    levels = 2 ** (bits - 1) - 1
    back = np.asarray(sa.dequantize(jnp.asarray(acc), bits, 1.0, count=count))
    np.testing.assert_array_equal(np.rint(back * levels).astype(np.int64), true)
    # without the count window the decode is garbage — both the seed's raw
    # int32 interpretation and a 1-count re-centering get the sums wrong
    raw = acc.astype(np.float32)  # what the seed code decoded from
    assert np.any(np.rint(raw).astype(np.int64) != true)
    naive = np.asarray(sa.dequantize(jnp.asarray(acc), bits, 1.0))
    assert np.any(np.rint(naive * levels).astype(np.int64) != true)


def test_field_modulus_shapes():
    assert sa.field_modulus(32, 1) == 1 << 32  # full int32 field: identity
    assert sa.field_modulus(16, 1) == 1 << 16
    assert sa.field_modulus(16, 3) == 1 << 18  # count rounded up to pow2
    assert sa.field_modulus(32, 64) == 1 << 32  # capped
    # to_field at the full field is the identity bit pattern
    q = jnp.asarray([-5, 0, 2 ** 31 - 1, -(2 ** 31)], jnp.int32)
    assert bool(jnp.all(sa.to_field(q, 1 << 32) == q))


def test_mask_session_carries_field_and_reduces():
    """MaskSession bundles the session's field modulus: ``reduce`` is the
    bit-packed wire encoding of the ``to_field`` residues at the session's
    wire width, and masks generated through the session object equal the
    free-function streams."""
    key = jax.random.PRNGKey(5)
    sess = sa.make_session(key, 6, modulus=sa.field_modulus(16, 6))
    assert sess.modulus == 1 << 19
    assert sess.wire_bits == 19
    q = jnp.asarray([-5, 0, (1 << 20) + 3], jnp.int32)
    words = sess.reduce(q)
    assert words.dtype == jnp.uint32
    assert words.shape == (sa.packed_words(3, sess.modulus),)
    # round-trips to the canonical residues, bit-exactly
    assert bool(jnp.all(sess.expand(words, 3)
                        == sa.to_field(q, sess.modulus)))
    # and the packed stream really is narrower than the int32 row
    assert np.asarray(words).nbytes < np.asarray(q).nbytes
    # the engines' construction point wires the spec's REAL field through
    # (and a leaf-sized session keeps the engine-wide field — partials
    # still combine into the full aggregate at the root)
    from repro.configs.base import FLConfig
    from repro.core.fl import aggregation as agg
    spec = agg.make_spec(FLConfig(secure_agg_bits=16), 8)
    assert spec.field_modulus == sa.field_modulus(16, 8) == 1 << 19
    esess = agg.make_mask_session(spec, key)
    assert esess.modulus == 1 << 19
    assert agg.make_mask_session(spec, key, num_slots=2).modulus == 1 << 19
    # session-object mask == free-function mask (same PRF tree)
    assert bool(jnp.all(sess.mask((17,), 2)
                        == sa.session_mask((17,), 2, 6, key)))
    assert bool(jnp.all(sess.recovery((17,), jnp.ones((6,)))
                        == jnp.zeros((17,), jnp.int32)))


def test_field_modulus_2_31_boundary():
    """C == 2^31 must not overflow the int32 scalar path (regression)."""
    bits, count = 24, 128
    assert sa.field_modulus(bits, count) == 1 << 31
    ups = [0.1 * jnp.ones((8,)) for _ in range(70)]  # C == 2^31 via next_pow2
    mean = sa.secure_aggregate(ups, 24, 4.0, seed=5)
    np.testing.assert_allclose(np.asarray(mean), 0.1, atol=1e-5)
    q = sa.quantize(jnp.asarray([-1.5, 0.0, 2.0]), bits, 4.0)
    back = sa.dequantize(q, bits, 4.0, count=count)
    np.testing.assert_allclose(np.asarray(back), [-1.5, 0.0, 2.0], atol=1e-5)
    wire = sa.to_field(q, 1 << 31)
    assert int(wire.min()) >= 0


def test_dequantize_count_identity_in_window():
    """Within the window the re-centering is a no-op (back-compat)."""
    key = jax.random.PRNGKey(4)
    x = jax.random.uniform(key, (300,), minval=-2.0, maxval=2.0)
    for count in (1, 7, 64):
        q = sa.quantize(x, 16, 2.0)
        back = sa.dequantize(q, 16, 2.0, count=count)
        base = sa.dequantize(q, 16, 2.0)
        assert bool(jnp.all(back == base))


# --- packed wire codec -------------------------------------------------------
@pytest.mark.parametrize("bits", list(range(1, 33)))
def test_pack_residues_round_trip_every_width(bits):
    """EVERY wire width 1..32, ragged sizes included: pack -> unpack is the
    identity on canonical residues, and the word stream has exactly
    ceil(D*bits/32) words (the dense layout, no per-element padding)."""
    modulus = 1 << bits
    rs = np.random.RandomState(bits)
    for D in (1, 31, 32, 33, 97):
        q = jnp.asarray(
            rs.randint(0, min(modulus, 1 << 31), size=D).astype(np.int32))
        q = sa.to_field(q, modulus) if bits == 32 else q
        words = sa.pack_residues(q, modulus)
        assert words.dtype == jnp.uint32
        assert words.shape == (-(-D * bits // 32),)
        back = sa.unpack_residues(words, D, modulus)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


def test_pack_residues_edge_moduli_round_trip():
    """The 2^31 and 2^32 field edges: full-range bit patterns survive."""
    q = jnp.asarray([-5, 0, 2 ** 31 - 1, -(2 ** 31), 123456789], jnp.int32)
    for modulus in (1 << 31, 1 << 32):
        canon = sa.to_field(q, modulus)
        back = sa.unpack_residues(sa.pack_residues(canon, modulus),
                                  canon.shape[0], modulus)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(canon))


def test_pack_residues_leading_axes():
    """Batched rows (leaf-batch ingest shape) pack along the last axis."""
    modulus = 1 << 19
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randint(0, modulus, size=(4, 70)).astype(np.int32))
    words = sa.pack_residues(q, modulus)
    assert words.shape == (4, sa.packed_words(70, modulus))
    for i in range(4):
        np.testing.assert_array_equal(
            np.asarray(sa.unpack_residues(words[i], 70, modulus)),
            np.asarray(q[i]))


def test_unpack_residues_word_count_mismatch_raises():
    modulus = 1 << 19
    words = sa.pack_residues(jnp.zeros((70,), jnp.int32), modulus)
    with pytest.raises(ValueError, match="packed"):
        sa.unpack_residues(words, 71, modulus)
    with pytest.raises(ValueError, match="power-of-two"):
        sa.wire_bits(100)


def test_packed_wire_wraparound_window_sums_decode_exact():
    """The wraparound regression, THROUGH the packed wire: residues that
    cross the packed stream and back accumulate (int32 wraparound, many
    wraps) to sums that dequantize(count=) decodes bit-equal to the
    unpacked path."""
    bits, count = 16, 4096
    C = sa.field_modulus(bits, count)
    rs = np.random.RandomState(1)
    vals = rs.randint(-20_000, 20_000, size=(count, 16)).astype(np.int32)
    wire = sa.to_field(jnp.asarray(vals), C)
    acc_direct = np.zeros(16, np.int32)
    acc_packed = np.zeros(16, np.int32)
    for row in wire:
        acc_direct = (acc_direct + np.asarray(row)).astype(np.int32)
        shipped = sa.unpack_residues(sa.pack_residues(row, C), 16, C)
        acc_packed = (acc_packed + np.asarray(shipped)).astype(np.int32)
    np.testing.assert_array_equal(acc_packed, acc_direct)
    levels = 2 ** (bits - 1) - 1
    back = np.asarray(
        sa.dequantize(jnp.asarray(acc_packed), bits, 1.0, count=count))
    np.testing.assert_array_equal(np.rint(back * levels).astype(np.int64),
                                  vals.sum(0))


# --- session masks (the traceable in-engine variant) -------------------------
def test_session_mask_matches_pairwise_mask():
    """Same PRF tree: session_mask(key=PRNGKey(seed)) == pairwise_mask."""
    key = jax.random.PRNGKey(11)
    n, shape = 7, (29,)
    for c in range(n):
        a = sa.pairwise_mask(shape, c, list(range(n)), 11)
        b = sa.session_mask(shape, c, n, key)
        assert bool(jnp.all(a == b))


@settings(deadline=None, max_examples=8)
@given(st.integers(2, 64), st.integers(0, 2 ** 31 - 1))
def test_session_mask_cancellation_property(n_slots, seed):
    """Bit-exact mask cancellation for random pairwise sessions of 2..64."""
    key = jax.random.PRNGKey(seed)
    shape = (17,)
    total = jnp.zeros(shape, jnp.int32)
    for s in range(n_slots):
        total = total + sa.session_mask(shape, s, n_slots, key)
    assert bool(jnp.all(total == 0))


@settings(deadline=None, max_examples=10)
@given(st.integers(2, 24), st.integers(0, 2 ** 31 - 1))
def test_masked_sum_equals_unmasked_under_wraparound_property(n, seed):
    """Masked modular sum == plain int32 wraparound sum, even when the
    quantized values are extreme enough that partial sums wrap."""
    key = jax.random.PRNGKey(seed)
    shape = (41,)
    # full-range int32 values: the unmasked running sum itself wraps
    qs = [jax.random.randint(jax.random.fold_in(key, c), shape,
                             -2 ** 31, 2 ** 31 - 1, jnp.int32)
          for c in range(n)]
    plain = qs[0]
    for q in qs[1:]:
        plain = plain + q
    skey = jax.random.fold_in(key, 0xABCD)
    masked = [q + sa.session_mask(shape, c, n, skey) for c, q in enumerate(qs)]
    agg = sa.aggregate_masked(masked)
    assert bool(jnp.all(agg == plain))


@settings(deadline=None, max_examples=10)
@given(st.floats(-0.999, 0.999), st.integers(0, 2 ** 31 - 1))
def test_stochastic_rounding_unbiased_property(value, seed):
    """E[dequant(quant(x, rng))] == x for coarse grids (unbiasedness)."""
    key = jax.random.PRNGKey(seed)
    x = jnp.full((40_000,), jnp.float32(value))
    q = sa.quantize(x, 8, 1.0, rng=key)  # 127 levels: large rounding step
    back = sa.dequantize(q, 8, 1.0)
    lsb = 1.0 / (2 ** 7 - 1)
    assert abs(float(back.mean()) - float(jnp.float32(value))) < lsb / 8


# --- dropout recovery / adversarial ------------------------------------------
@pytest.mark.parametrize("n,drop", [(4, 1), (8, 3), (12, 5)])
def test_dropout_recovery_decodes_exact_survivor_sum(n, drop):
    """Drop 1..k clients from a masked session: with the recovery shares the
    decode is EXACT over survivors; without them it is garbage (the masks
    actually hide the updates)."""
    key = jax.random.PRNGKey(n * 31 + drop)
    shape = (65,)
    qs = [sa.quantize(0.4 * jax.random.normal(jax.random.fold_in(key, c), shape),
                      24, 4.0) for c in range(n)]
    skey = jax.random.fold_in(key, 0xD0)
    masked = [q + sa.session_mask(shape, c, n, skey) for c, q in enumerate(qs)]
    dropped = set(range(drop))  # kill the first `drop` contributors
    present = jnp.asarray([0.0 if c in dropped else 1.0 for c in range(n)])
    partial = sum(m for c, m in enumerate(masked) if c not in dropped)
    want = sum(q for c, q in enumerate(qs) if c not in dropped)
    # (a) recovery shares cancel the un-paired masks: exact survivor sum
    recovered = partial + sa.recovery_mask(shape, present, n, skey)
    assert bool(jnp.all(recovered == want))
    # (b) without recovery the decode is garbage: the un-cancelled masks are
    # full-range int32, so almost no element survives unchanged
    assert float(jnp.mean((partial == want).astype(jnp.float32))) < 0.02


def test_single_masked_update_hides_plaintext():
    """Adversarial server view: one masked update reveals ~nothing elementwise
    and recovery shares for NON-dropped clients do not unmask anyone."""
    key = jax.random.PRNGKey(17)
    n, shape = 6, (257,)
    q = sa.quantize(0.5 * jax.random.normal(key, shape), 24, 4.0)
    skey = jax.random.fold_in(key, 1)
    masked = q + sa.session_mask(shape, 0, n, skey)
    assert float(jnp.mean((masked == q).astype(jnp.float32))) < 0.01
    # recovery for an all-present session is identically zero — the server
    # cannot request shares that would strip a live client's mask
    zero = sa.recovery_mask(shape, jnp.ones((n,)), n, skey)
    assert bool(jnp.all(zero == 0))


def test_round_step_scale_guards_overflow():
    """Fixed-point scale leaves headroom for a cohort-sized sum."""
    from repro.configs.base import FLConfig
    from repro.core.fl.round import _sa_scale
    fl = FLConfig(secure_agg_bits=32, secure_agg_range=4.0)
    for cohort in (1, 64, 4096):
        scale = _sa_scale(fl, cohort)
        per_client_max = 4.0 * scale + 1  # + stochastic-round bit
        assert per_client_max * cohort <= 2 ** 31 - 1
