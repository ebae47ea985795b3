"""Upload compression: sketched/subsampled client updates in the masked field.

The contracts this file enforces (the PR's acceptance bar):

  * the PRF-derived operators are UNBIASED: over the operator seed,
    ``E[expand(compress(x))] = x`` for both subsample and sketch modes;
  * rate 1.0 canonicalizes to the identity spec and follows the legacy
    packed path BYTE-for-byte — all four mask modes, flat server and both
    tier topologies, through nested client/whole-leaf dropout;
  * the compressed tier decodes bit-identically to the compressed flat
    server (sketch-domain accumulation survives destination sharding);
  * a ClientPush encoded under a different compression spec is rejected
    with an error naming BOTH specs (it lives in another sketch domain);
  * the batched (non-streaming) engines refuse active compression up
    front instead of silently buffering raw f32;
  * a FaultInjector retry that crosses a session roll re-derives the new
    session's operators and the result matches a clean replay to the bit;
  * ``enclave_wire_bits`` quantizes the tee uplink and the
    ``upload_bytes{lane=...}`` telemetry meters every wire.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import FLConfig
from repro.core.fl import aggregation as agg
from repro.core.fl import compression as comp
from repro.core.fl.async_fl import AsyncServer
from repro.core.fl.faults import FaultInjector, FaultPlan, FaultSpec
from repro.core.fl.hierarchy import ShardedAsyncServer

SHAPES = {"emb": (40, 16), "w1": (700,), "w2": (300, 3), "b": (5,)}
D = 2245
CHUNK = 1000
FL = FLConfig(clip_norm=1.0, server_lr=1.0, secure_agg_bits=32)
MODES = ("off", "tee", "tee_stream", "client")
STREAMING = ("off", "tee_stream", "client")
SKETCH = dict(compress_mode="sketch", compress_rate=0.25)

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="aggregation tier needs >=2 devices (forced host devices OK)")


def _params():
    return {k: jnp.zeros(s, jnp.float32) for k, s in SHAPES.items()}


def _deltas(n, seed=0):
    key = jax.random.PRNGKey(seed)
    out = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        out.append({name: 0.1 * jax.random.normal(
            jax.random.fold_in(k, j), s)
            for j, (name, s) in enumerate(SHAPES.items())})
    return out


def _diff(a, b):
    return max(float(np.abs(np.asarray(x) - np.asarray(y)).max())
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _lane_bytes(tel, lane):
    return sum(v for (n, lk), v in tel.counters().items()
               if n == "upload_bytes" and ("lane", lane) in lk)


# --- spec / config validation ------------------------------------------------
def test_spec_canonicalizes_rate_one_to_identity():
    assert comp.CompressionSpec().identity
    assert comp.CompressionSpec("sketch", 1.0) == comp.CompressionSpec()
    assert comp.CompressionSpec("none", 0.5) == comp.CompressionSpec()
    s = comp.CompressionSpec("sketch", 0.25)
    assert not s.identity and s.describe() == "sketch@rate=0.25"
    with pytest.raises(ValueError, match="compress_mode"):
        comp.CompressionSpec("topk", 0.5)
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError, match="compress_rate"):
            comp.CompressionSpec("sketch", bad)


@pytest.mark.parametrize("bad,msg", [
    (dict(compress_mode="topk"), "compress_mode"),
    (dict(compress_mode="sketch", compress_rate=0.0), "compress_rate"),
    (dict(compress_mode="sketch", compress_rate=0.5,
          secure_agg_bits=0), "secure_agg_bits"),
    (dict(enclave_wire_bits=1), "enclave_wire_bits"),
    (dict(enclave_wire_bits=33), "enclave_wire_bits"),
])
def test_flconfig_rejects_incoherent_compression(bad, msg):
    with pytest.raises(ValueError, match=msg):
        dataclasses.replace(FL, **bad)


def test_flconfig_accepts_coherent_compression():
    dataclasses.replace(FL, **SKETCH)
    dataclasses.replace(FL, compress_mode="subsample", compress_rate=0.5)
    dataclasses.replace(FL, enclave_wire_bits=8)
    FLConfig(compress_mode="sketch")  # rate 1.0: identity, no field needed


# --- wire widths -------------------------------------------------------------
def test_wire_chunks_widths():
    plan_f = agg.make_param_plan(_params())
    plan_c = agg.make_param_plan(_params(), chunk_elems=CHUNK)
    ident = comp.CompressionSpec()
    for plan in (plan_f, plan_c):
        assert comp.wire_chunks(ident, plan.chunks) == tuple(
            comp.WireChunk(c.size, c.padded, c.size) for c in plan.chunks)
    sk = comp.CompressionSpec("sketch", 0.25)
    sub = comp.CompressionSpec("subsample", 0.25)
    for cspec in (sk, sub):
        for plan in (plan_f, plan_c):
            for ck, wc in zip(plan.chunks, comp.wire_chunks(
                    cspec, plan.chunks)):
                m = max(1, math.ceil(0.25 * ck.size))
                assert wc.size == m < ck.size
                # sketch rotates over whole Hadamard blocks
                want_full = (-(-ck.size // comp.SKETCH_BLOCK)
                             * comp.SKETCH_BLOCK
                             if cspec.mode == "sketch" else ck.size)
                assert wc.full == want_full
                # wire padding follows the plan's own padding rule
                if ck.padded == ck.size:
                    assert wc.padded == m
                else:
                    assert wc.padded == -(-m // comp.SKETCH_BLOCK) \
                        * comp.SKETCH_BLOCK


# --- the estimator property: E[expand(compress(x))] = x ----------------------
@pytest.mark.parametrize("cmode", ("subsample", "sketch"))
def test_operators_are_unbiased(cmode):
    """Monte-Carlo over the PRF operator seed: the decoded estimate is
    unbiased coordinate-wise (within 6 standard errors)."""
    size, rate, nseeds = 300, 0.25, 4096
    x = jnp.asarray(np.random.default_rng(0).uniform(-1.0, 1.0, size),
                    jnp.float32)

    def one(k):
        op = comp.chunk_operators(k, cmode, size, rate)
        return comp.expand(comp.compress(x, op), op, size)

    keys = jax.random.split(jax.random.PRNGKey(7), nseeds)
    est = np.asarray(jax.jit(jax.vmap(one))(keys))
    mean, sem = est.mean(axis=0), est.std(axis=0) / math.sqrt(nseeds)
    assert np.all(np.abs(mean - np.asarray(x)) < 6.0 * sem + 1e-4)


@pytest.mark.parametrize("n,m,tie_every", [(1000, 250, 7), (777, 300, 3),
                                            (4096, 4096, 0), (5000, 1, 2)])
def test_smallest_m_equals_stable_argsort_selection(n, m, tie_every):
    """The sort-free selection keeps exactly ``sort(argsort(ranks)[:m])``,
    ties at the threshold taken in position order."""
    ranks = jax.random.randint(jax.random.PRNGKey(n), (n,), -2 ** 31,
                               2 ** 31 - 1, jnp.int32)
    if tie_every:
        ranks = ranks.at[::tie_every].set(ranks[1])
    want = np.sort(np.argsort(np.asarray(ranks), kind="stable")[:m])
    got = np.asarray(jax.jit(comp.smallest_m, static_argnums=1)(ranks, m))
    np.testing.assert_array_equal(got, want)


def test_sketch_rotation_is_orthonormal_and_self_inverse():
    x = jax.random.normal(jax.random.PRNGKey(1), (1024,))
    op = comp.chunk_operators(jax.random.PRNGKey(2), "sketch", 1024, 1.0)
    y = comp.block_rotate(x, op.signs)
    assert abs(float(jnp.linalg.norm(y)) - float(jnp.linalg.norm(x))) < 1e-3
    assert _diff(comp.block_rotate_t(y, op.signs), x) < 1e-5


# --- rate 1.0 == the legacy packed path, to the bit --------------------------
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("cmode", ("subsample", "sketch"))
def test_rate_one_bit_identical_flat(mode, cmode):
    """compress_rate=1.0 canonicalizes to the identity spec: same bytes,
    same decode, all four mask modes, with dropout recovery."""
    fl1 = dataclasses.replace(FL, compress_mode=cmode, compress_rate=1.0)
    srvs = [AsyncServer(_params(), fl, buffer_size=4, mask_mode=mode,
                        staleness_mode="constant") for fl in (FL, fl1)]
    assert srvs[1]._spec.compression.identity
    ds = _deltas(4)
    frng = jax.random.PRNGKey(11)
    for srv in srvs:
        for s in (0, 2, 3):
            if mode == "client":
                srv.push_encoded(srv.encode_push(ds[s], srv.version,
                                                 slot=s))
            else:
                srv.push(ds[s], srv.version)
        srv.flush(rng=frng)
    assert srvs[0].version == srvs[1].version == 1
    assert _diff(srvs[0].params, srvs[1].params) == 0.0


@needs_mesh
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("two_level", [False, True],
                         ids=["flat-session", "session-tree"])
def test_rate_one_bit_identical_tier(mode, two_level):
    """Rate-1.0 parity on the sharded tier through nested client +
    whole-leaf dropout (keep=(0,): leaf 1 dies entirely)."""
    fl1 = dataclasses.replace(FL, compress_mode="sketch",
                              compress_rate=1.0)
    srvs = [ShardedAsyncServer(_params(), fl, num_leaves=2, leaf_buffer=2,
                               mask_mode=mode, two_level=two_level,
                               staleness_mode="constant")
            for fl in (FL, fl1)]
    ds = _deltas(4)
    frng = jax.random.PRNGKey(11)
    for srv in srvs:
        if mode == "client":
            srv.push_encoded(srv.encode_push(ds[0], srv.version, slot=0))
        else:
            srv.push(ds[0], srv.version, slots=[0])
        srv.flush(rng=frng)
    assert srvs[0].version == srvs[1].version == 1
    assert _diff(srvs[0].params, srvs[1].params) == 0.0


# --- compressed end-to-end: deterministic, near-exact, short wire ------------
@pytest.mark.parametrize("mode", STREAMING)
@pytest.mark.parametrize("cmode", ("subsample", "sketch"))
def test_compressed_flat_end_to_end(mode, cmode):
    """Every streaming mask mode aggregates in the sketch domain: buffers
    sit at the wire width, the decode is deterministic, and the estimate
    tracks the exact aggregate."""
    flc = dataclasses.replace(FL, compress_mode=cmode, compress_rate=0.25)
    mk = lambda fl: AsyncServer(_params(), fl, buffer_size=4,
                                mask_mode=mode, staleness_mode="constant")
    srv, twin, exact = mk(flc), mk(flc), mk(FL)
    wire = agg.plan_wire_chunks(srv._spec, srv.plan)
    assert tuple(b.shape[-1] for b in srv._bufs) == tuple(
        wc.padded for wc in wire)
    assert sum(wc.size for wc in wire) <= math.ceil(0.25 * D) + 1
    ds = _deltas(4)
    frng = jax.random.PRNGKey(11)
    for s in (0, 2, 3):  # with a dropout recovery in the masked field
        for sv in (srv, twin, exact):
            if mode == "client":
                sv.push_encoded(sv.encode_push(ds[s], sv.version, slot=s))
            else:
                sv.push(ds[s], sv.version)
    for sv in (srv, twin, exact):
        sv.flush(rng=frng)
    assert srv.version == 1
    assert _diff(srv.params, twin.params) == 0.0  # seeded: fully replayable
    err = _diff(srv.params, exact.params)
    assert 0.0 < err < 0.5  # unbiased estimate of a ~0.1-scale aggregate


@needs_mesh
@pytest.mark.parametrize("mode", ("client", "tee_stream"))
@pytest.mark.parametrize("two_level", [False, True],
                         ids=["flat-session", "session-tree"])
def test_compressed_tier_matches_flat(mode, two_level):
    """Sketch-domain accumulation commutes with destination sharding: the
    compressed tier decodes bit-identically to the compressed flat
    server (operators are keyed by the ENGINE session key)."""
    flc = dataclasses.replace(FL, **SKETCH)
    tier = ShardedAsyncServer(_params(), flc, num_leaves=2, leaf_buffer=2,
                              mask_mode=mode, two_level=two_level,
                              staleness_mode="constant")
    flat = AsyncServer(_params(), flc, buffer_size=4, mask_mode=mode,
                       staleness_mode="constant")
    ds = _deltas(4)
    frng = jax.random.PRNGKey(11)
    for s in (0, 2, 3):
        if mode == "client":
            tier.push_encoded(tier.encode_push(ds[s], tier.version,
                                               slot=s))
            flat.push_encoded(flat.encode_push(ds[s], flat.version,
                                               slot=s))
        else:
            tier.push(ds[s], tier.version, slots=[s])
            flat.push(ds[s], flat.version)
    tier.flush(rng=frng)
    flat.flush(rng=frng)
    assert tier.version == flat.version == 1
    err = _diff(tier.params, flat.params)
    if mode == "client":
        # integer field end to end: the sharded sum is exact
        assert err == 0.0
    else:
        # tee_stream adds the enclave noise in float, and the mesh sums
        # it in a different reduction order — parity is numerical (ulps)
        assert err < 1e-6


# --- protocol guards ---------------------------------------------------------
def test_push_encoded_rejects_compression_mismatch():
    """A row encoded in another sketch domain must never be summed in:
    the error names BOTH specs so the operator can fix the config skew."""
    flc = dataclasses.replace(FL, **SKETCH)
    plain = AsyncServer(_params(), FL, buffer_size=4, mask_mode="client")
    packed = AsyncServer(_params(), flc, buffer_size=4, mask_mode="client")
    d = _deltas(1)[0]
    with pytest.raises(ValueError) as e:
        packed.push_encoded(plain.encode_push(d, 0, slot=0))
    assert "identity" in str(e.value) and "sketch@rate=0.25" in str(e.value)
    with pytest.raises(ValueError) as e:
        plain.push_encoded(packed.encode_push(d, 0, slot=0))
    assert "identity" in str(e.value) and "sketch@rate=0.25" in str(e.value)


def test_batched_engines_refuse_active_compression():
    flc = dataclasses.replace(FL, **SKETCH)
    with pytest.raises(ValueError, match="STREAMING|streaming"):
        AsyncServer(_params(), flc, buffer_size=4, mask_mode="tee")
    # rate 1.0 is the identity spec: the batched engine stays usable
    AsyncServer(_params(), dataclasses.replace(
        FL, compress_mode="sketch", compress_rate=1.0),
        buffer_size=4, mask_mode="tee")


@needs_mesh
def test_batched_tier_refuses_active_compression():
    flc = dataclasses.replace(FL, **SKETCH)
    with pytest.raises(ValueError, match="STREAMING|streaming"):
        ShardedAsyncServer(_params(), flc, num_leaves=2, leaf_buffer=2,
                           mask_mode="tee")


# --- faults: a retry across a session roll re-derives the operators ----------
def test_retry_after_session_roll_rederives_operators():
    """A delayed compressed push that lands after its session rolled is
    re-encoded under the NEW session — new masks AND new sketch operators
    — and the whole run replays bit-for-bit from the survivor record."""
    flc = dataclasses.replace(FL, **SKETCH)
    mk = lambda: AsyncServer(_params(), flc, buffer_size=2,
                             mask_mode="client", strict=False,
                             staleness_mode="constant")
    srv = mk()
    inj = FaultInjector(srv, FaultPlan(FaultSpec(p_delay=1.0,
                                                 delay_pushes=1, seed=0)))
    ds = _deltas(4)
    inj.push(ds[0], srv.version)  # held in flight, encoded under session 0
    # two out-of-band pushes fill the buffer: the session rolls to v1
    srv.push_encoded(srv.encode_push(ds[2], srv.version, slot=0))
    srv.push_encoded(srv.encode_push(ds[3], srv.version, slot=1))
    assert srv.version == 1
    inj.push(ds[1], srv.version)  # tick: the held push delivers, stale
    inj.flush(force=True)
    assert any(site == "retry" for site, _ in inj.plan.trace)
    assert len(inj.delivered) == 2
    assert srv.version == 2
    # clean replay: session 0 = the out-of-band pair, session 1 = the
    # injector's survivors at their recorded slots
    ref = mk()
    ref.push_encoded(ref.encode_push(ds[2], 0, slot=0))
    ref.push_encoded(ref.encode_push(ds[3], 0, slot=1))
    for ver in sorted(inj.survivors):
        assert ref.version == ver
        for slot, (seq, cv) in sorted(inj.survivors[ver].items()):
            ref.push_encoded(ref.encode_push(ds[seq], cv, slot=slot))
        if ref.version == ver:
            ref.flush(force=True)
    assert _diff(srv.params, ref.params) == 0.0


# --- enclave wire + telemetry ------------------------------------------------
def test_enclave_wire_quantizes_the_tee_uplink():
    """enclave_wire_bits=8 rides a packed 8-bit field to the enclave: the
    decode moves (really quantized) but stays within a step of raw f32,
    and the metered enclave bytes are ~1/4 of the raw wire."""
    from repro.core.telemetry import Telemetry
    fle = dataclasses.replace(FL, enclave_wire_bits=8)
    srv8 = AsyncServer(_params(), fle, buffer_size=4,
                       mask_mode="tee_stream", staleness_mode="constant",
                       telemetry=Telemetry())
    raw = AsyncServer(_params(), FL, buffer_size=4,
                      mask_mode="tee_stream", staleness_mode="constant",
                      telemetry=Telemetry())
    ds = _deltas(4)
    for d in ds:
        srv8.push(d, srv8.version)
        raw.push(d, raw.version)
    assert srv8.version == raw.version == 1
    err = _diff(srv8.params, raw.params)
    assert 0.0 < err < 0.05
    ebytes = _lane_bytes(srv8.telemetry, "enclave")
    assert 0 < ebytes < 0.3 * (4 * 4 * D)  # 8/32 bits + pack overhead
    assert _lane_bytes(raw.telemetry, "enclave") == 0


def test_upload_bytes_lanes_metered_at_both_seams():
    """encode_push and push_encoded each meter the masked wire; the lane
    label says whether the session compresses."""
    from repro.core.telemetry import Telemetry
    flc = dataclasses.replace(FL, **SKETCH)
    csrv = AsyncServer(_params(), flc, buffer_size=4, mask_mode="client",
                       telemetry=Telemetry())
    psrv = AsyncServer(_params(), FL, buffer_size=4, mask_mode="client",
                       telemetry=Telemetry())
    d = _deltas(1)[0]
    csrv.push_encoded(csrv.encode_push(d, 0, slot=0))
    psrv.push_encoded(psrv.encode_push(d, 0, slot=0))
    wire = agg.plan_wire_chunks(csrv._spec, csrv.plan)
    cbytes = 4 * sum(wc.padded for wc in wire)
    assert _lane_bytes(csrv.telemetry, "compressed") == 2 * cbytes
    assert _lane_bytes(csrv.telemetry, "packed") == 0
    full = agg.plan_wire_chunks(psrv._spec, psrv.plan)
    assert _lane_bytes(psrv.telemetry, "packed") == 2 * 4 * sum(
        wc.padded for wc in full)
    assert _lane_bytes(psrv.telemetry, "compressed") == 0
    # the compressed wire really is ~rate of the packed wire
    assert cbytes <= 0.3 * 4 * sum(wc.padded for wc in full)
