"""The engines' spans below the facade, and the names on the device side.

* ``encode`` (the session key, the per-slot rng and the encode's dispatch)
  and ``store`` (the row write's dispatch and the slot bookkeeping) sit
  under ``push`` / ``encode_push`` / ``push_encoded``; ``store`` closes
  before the flush's ``decode`` opens, so ``push`` splits exactly into its
  own time and its children's.
* Recorded spans also land in the profiler's trace as host events.
* The jitted programs carry named scopes (``encode/clip``,
  ``encode/quantize_mask``, ``store``, ``sum``, ``recover``, ``apply``,
  the tier's ``combine``), which a lowering's locations show.
"""
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import FLConfig
from repro.core import telemetry as tele
from repro.core.fl.async_fl import AsyncServer
from repro.core.fl.hierarchy import ShardedAsyncServer
from repro.core.telemetry import Telemetry

D = 41
B = 3
FL = FLConfig(clip_norm=1.0, server_lr=1.0, secure_agg_bits=24)
CHILDREN = {"encode", "store", "decode"}


def _params():
    return {"w": jnp.zeros((D,), jnp.float32),
            "b": jnp.zeros((3,), jnp.float32)}


def _delta(i):
    k = jax.random.fold_in(jax.random.PRNGKey(7), i)
    return {"w": 0.1 * jax.random.normal(k, (D,)),
            "b": 0.1 * jax.random.normal(jax.random.fold_in(k, 1), (3,))}


def _engine(mode, tel=None, buffer_size=B):
    return AsyncServer(_params(), FL, buffer_size=buffer_size,
                       mask_mode=mode, telemetry=tel)


def _by_sid(tel):
    return {sp.sid: sp for sp in tel.spans}


def _parent_name(sp, sids):
    return None if sp.parent is None else sids[sp.parent].name


def _end(sp):
    return sp.t0_ns + sp.dur_ns


def _one_round(eng, mode):
    """Fill one session: through ``push`` in the TEE modes, through the
    client/server halves (as a SecAgg+ fleet does) in client mode."""
    for i in range(B):
        if mode == "client":
            eng.push_encoded(eng.encode_push(_delta(i), eng.version))
        else:
            eng.push(_delta(i), eng.version)


@pytest.mark.parametrize("mode", ["tee_stream", "off", "tee", "client"])
def test_store_span_under_its_ingest_span(mode):
    tel = Telemetry(record_spans=True)
    eng = _engine(mode, tel)
    _one_round(eng, mode)
    sids = _by_sid(tel)
    stores = [sp for sp in tel.spans if sp.name == "store"]
    assert len(stores) == B
    want = "push_encoded" if mode == "client" else "push"
    assert {_parent_name(sp, sids) for sp in stores} == {want}
    assert [sp.labels["slot"] for sp in stores] == list(range(B))
    assert all(sp.labels["round"] == 0 and sp.labels["eid"] == eng._eid
               for sp in stores)


@pytest.mark.parametrize("mode", ["tee_stream", "client"])
def test_encode_span_under_push_or_encode_push(mode):
    tel = Telemetry(record_spans=True)
    eng = _engine(mode, tel)
    _one_round(eng, mode)
    sids = _by_sid(tel)
    encodes = [sp for sp in tel.spans if sp.name == "encode"]
    assert len(encodes) == B
    want = "encode_push" if mode == "client" else "push"
    assert {_parent_name(sp, sids) for sp in encodes} == {want}
    assert [sp.labels["slot"] for sp in encodes] == list(range(B))


def test_unstreamed_tee_has_no_encode_span():
    tel = Telemetry(record_spans=True)
    _one_round(_engine("tee", tel), "tee")
    assert not [sp for sp in tel.spans if sp.name == "encode"]


@pytest.mark.parametrize("mode", ["tee_stream", "client", "tee"])
def test_store_closes_before_decode_opens(mode):
    tel = Telemetry(record_spans=True)
    eng = _engine(mode, tel)
    _one_round(eng, mode)
    sids = _by_sid(tel)
    (decode,) = [sp for sp in tel.spans if sp.name == "decode"]
    assert _parent_name(decode, sids) == ("push_encoded" if mode == "client"
                                          else "push")
    last_store = [sp for sp in tel.spans if sp.name == "store"][-1]
    assert last_store.parent == decode.parent
    assert _end(last_store) <= decode.t0_ns
    assert all(_parent_name(sp, sids) != "store" for sp in tel.spans)


def test_push_splits_into_self_encode_store_decode():
    """``push`` = its own time + ``encode`` + ``store`` + ``decode``,
    exactly, on the records (tee_stream, the full session included)."""
    from chipbench.trace import self_time_ns

    tel = Telemetry(record_spans=True)
    eng = _engine("tee_stream", tel)
    _one_round(eng, "tee_stream")
    _one_round(eng, "tee_stream")
    pushes = {sp.sid: sp for sp in tel.spans if sp.name == "push"}
    kids = [sp for sp in tel.spans if sp.parent in pushes]
    assert {sp.name for sp in kids} == CHILDREN
    total = sum(sp.dur_ns for sp in pushes.values())
    assert total == self_time_ns(tel.spans, ("push",)) + sum(
        sp.dur_ns for sp in kids)
    for name in CHILDREN:
        assert sum(sp.dur_ns for sp in kids if sp.name == name) > 0


def test_spans_off_record_nothing_and_share_the_null_span():
    tel = Telemetry(record_spans=False)
    eng = _engine("tee_stream", tel)
    assert eng._span("encode", slot=0) is tele._NULL_SPAN
    _one_round(eng, "tee_stream")
    assert tel.spans == []
    assert tel.value("stored_contributions", **eng._tl) == B


def test_spans_in_the_profiler_trace(tmp_path):
    """One tee_stream push that fills the session, profiled: the trace's
    host thread holds ``push`` with ``encode`` and ``store`` inside it, and
    ``decode``."""
    from jax.profiler import ProfileData

    tel = Telemetry(record_spans=True)
    eng = _engine("tee_stream", tel)
    _one_round(eng, "tee_stream")  # compiles every program first
    for i in range(B - 1):
        eng.push(_delta(i), eng.version)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.push(_delta(B), eng.version)
        jax.block_until_ready(eng.params)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in CHILDREN | {"push"}:
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    assert set(events) == CHILDREN | {"push"}
    (push,) = events["push"]
    for name in CHILDREN:
        (inner,) = events[name]
        assert push[0] <= inner[0] <= inner[1] <= push[1], name
    assert events["store"][0][1] <= events["decode"][0][0]


def _spy(obj, name, seen):
    """Record the lowering (with locations) of the jitted ``obj.<name>``
    on its next call."""
    fn = getattr(obj, name)

    def call(*args):
        seen[name] = fn.lower(*args).as_text(debug_info=True)
        return fn(*args)

    setattr(obj, name, call)


@pytest.mark.parametrize("scope", ["encode/clip/", "encode/quantize_mask/"])
def test_encode_program_scopes(scope):
    eng, seen = _engine("tee_stream"), {}
    _spy(eng, "_masked_encode", seen)
    eng.push(_delta(0), 0)
    assert f"jit(_masked_encode)/{scope}" in seen["_masked_encode"]


def test_store_program_scope():
    eng, seen = _engine("tee_stream"), {}
    _spy(eng, "_write_row", seen)
    eng.push(_delta(0), 0)
    assert "jit(_write_row)/store/" in seen["_write_row"]


@pytest.mark.parametrize("recover", [True, False])
def test_flush_program_scopes(recover):
    """``sum`` and ``apply`` in both flush programs; ``recover`` only in
    the one that sweeps absent slots."""
    eng, seen = _engine("client"), {}
    eng._flush_step = eng._build_flush_step()
    _spy(eng, "_step", seen)
    _spy(eng, "_flush_step", seen)
    for i in range(B if not recover else B - 1):
        eng.push(_delta(i), eng.version)
    if recover:
        eng.flush(force=True)
    text = seen["_flush_step" if recover else "_step"]
    assert "jit(step)/sum/" in text and "jit(step)/apply/" in text
    assert ("/recover/" in text) == recover


def test_tier_program_scopes():
    """The tier's ingest encodes and stores under ``encode`` / ``store``;
    its two-level flush combines the leaves under ``combine``."""
    srv = ShardedAsyncServer(_params(), FL, num_leaves=2, leaf_buffer=2,
                             mask_mode="tee_stream", two_level=True)
    seen = {}
    _spy(srv, "_ingest_sharded", seen)
    _spy(srv, "_step", seen)
    for i in range(4):
        srv.push(_delta(i), srv.version)
    assert srv.version == 1
    ingest = seen["_ingest_sharded"]
    assert "/encode/" in ingest and "/store/" in ingest
    assert "/encode/vmap(clip)/" in ingest  # the rows' encode is vmapped
    assert "/combine/" in seen["_step"]
