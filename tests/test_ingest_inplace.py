"""The store writes each row in place, with the stores in flight bounded.

* Every store donates the contribution buffer: after a ``push`` /
  ``push_encoded`` the buffer it was handed is deleted, and the compiled
  store aliases it (no whole-buffer ``copy``, no out-of-range ``select``
  of the row).
* Pinned, out-of-order slots land in their own rows, and what is released
  is bit-identical to an engine whose writes are not donated.
* Before dispatching a store the host waits for the one two stores back,
  inside the ``store`` span, so at most two stores are unfinished when
  ``store`` returns; ``store_waits`` counts each dispatch that had to wait.
"""
import collections
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import FLConfig
from repro.core.fl.async_fl import AsyncServer
from repro.core.telemetry import Telemetry

D = 300
B = 4
FL = FLConfig(clip_norm=1.0, server_lr=1.0, secure_agg_bits=24)
# (mask_mode, engine kwargs): the streamed engines store through
# ``_write_row``, the others through ``_write``
ENGINES = {
    "tee_stream": ("tee_stream", {}),
    "client": ("client", {}),
    "off_stream": ("off", {}),
    "tee": ("tee", {}),
    "off": ("off", {"stream_encode": False}),
}


def _params():
    return {"w": jnp.zeros((D,), jnp.float32),
            "b": jnp.zeros((5,), jnp.float32)}


def _delta(i):
    k = jax.random.fold_in(jax.random.PRNGKey(11), i)
    return {"w": 0.1 * jax.random.normal(k, (D,)),
            "b": 0.1 * jax.random.normal(jax.random.fold_in(k, 1), (5,))}


def _engine(name, tel=None):
    mode, kw = ENGINES[name]
    return AsyncServer(_params(), FL, buffer_size=B, mask_mode=mode,
                       staleness_mode="constant", telemetry=tel, **kw)


def _store_name(eng):
    return "_write_row" if eng._streaming else "_write"


def _push(eng, i, slot=None):
    """One contribution through the engine's ingest path (the client and
    server halves in client mode)."""
    if eng.mask_mode == "client":
        return eng.push_encoded(eng.encode_push(_delta(i), eng.version,
                                                slot=slot))
    return eng.push(_delta(i), eng.version, slot=slot)


def _undonated(eng):
    """The store as it was before donation: ``.at[slot].set`` into a copy
    of the buffer (a test-local reference)."""
    if eng._streaming:
        def write(bufs, stal, wts, norms, clips, slot, rows, s, w, nrm,
                  clipped):
            return (tuple(b.at[slot].set(r) for b, r in zip(bufs, rows)),
                    stal.at[slot].set(jnp.asarray(s, jnp.float32)),
                    wts.at[slot].set(w), norms.at[slot].set(nrm),
                    clips.at[slot].set(clipped))
    else:
        plan = eng.plan

        def write(bufs, stal, valid, slot, delta, s):
            rows = plan.chunk_arrays(delta, pad=True)
            return (tuple(b.at[slot].set(r) for b, r in zip(bufs, rows)),
                    stal.at[slot].set(jnp.asarray(s, jnp.float32)),
                    valid.at[slot].set(1.0))
    return jax.jit(write)


def _compiled_store(eng, fn):
    """Compiled text of ``fn`` for the arguments of the engine's next
    store (lowered before the store runs, so nothing is donated yet)."""
    name, seen = _store_name(eng), {}
    real = getattr(eng, name)

    def spy(*args):
        seen["text"] = fn.lower(*args).compile().as_text()
        return real(*args)

    setattr(eng, name, spy)
    _push(eng, 0)
    setattr(eng, name, real)
    return seen["text"]


def _buffer_copies(text, buf):
    """HLO lines that copy a whole buffer of ``buf``'s shape, and lines
    that ``select`` a row of it."""
    dt = {jnp.dtype(jnp.int32): "s32", jnp.dtype(jnp.float32): "f32"}
    rows, width = buf.shape
    whole = re.compile(rf"= {dt[buf.dtype]}\[{rows},{width}\]\S* copy\(")
    row = re.compile(rf"= {dt[buf.dtype]}\[1,{width}\]\S* select\(")
    return ([ln for ln in text.splitlines() if whole.search(ln)],
            [ln for ln in text.splitlines() if row.search(ln)])


@pytest.mark.parametrize("name", list(ENGINES))
def test_store_deletes_the_buffer_it_was_handed(name):
    eng = _engine(name)
    for i in range(B + 1):  # across a release too
        before = eng._bufs
        assert _push(eng, i)
        assert all(b.is_deleted() for b in before)
        assert not any(b.is_deleted() for b in eng._bufs)
    assert eng.version == 1


@pytest.mark.parametrize("name", ["tee_stream", "tee"])
def test_compiled_store_aliases_the_buffer(name):
    eng = _engine(name)
    text = _compiled_store(eng, getattr(eng, _store_name(eng)))
    assert "input_output_alias={" in text
    assert _buffer_copies(text, eng._bufs[0]) == ([], [])


@pytest.mark.parametrize("name", ["tee_stream", "tee"])
def test_undonated_store_shows_the_copy(name):
    """The check above can fail: the undonated write holds both the
    buffer's copy and the row's ``select``."""
    eng = _engine(name)
    text = _compiled_store(eng, _undonated(eng))
    whole, row = _buffer_copies(text, eng._bufs[0])
    assert whole and row
    assert "input_output_alias={" not in text


@pytest.mark.parametrize("name", list(ENGINES))
def test_pinned_slots_match_undonated_writes(name):
    """Out-of-order pinned slots land in their own rows; the buffer and
    the release match an engine whose writes copy, to the bit."""
    got, ref = _engine(name), _engine(name)
    store = _store_name(ref)
    setattr(ref, store, _undonated(ref))
    order = [3, 0, 2]
    for i, slot in enumerate(order):
        assert _push(got, i, slot) and _push(ref, i, slot)
        for b, r in zip(got._bufs, ref._bufs):
            assert bool(jnp.all(b == r))
    filled = jnp.any(got._bufs[0] != 0, axis=1)
    assert [bool(x) for x in filled] == [s in order for s in range(B)]
    assert _push(got, B - 1, 1) and _push(ref, B - 1, 1)
    assert got.version == ref.version == 1
    for a, b in zip(jax.tree.leaves(got.params), jax.tree.leaves(ref.params)):
        assert bool(jnp.all(a == b))
    assert any(bool(jnp.any(a != 0)) for a in jax.tree.leaves(got.params))


class _Pending:
    """A store's output that stays unfinished until the host blocks on it
    (a device that never catches up by itself), or until ``finish``."""

    def __init__(self, tel, log):
        self.tel, self.done, self.blocked_at = tel, False, None
        log.append(self)

    def is_ready(self):
        return self.done

    def finish(self):
        self.done = True

    def block_until_ready(self):
        self.blocked_at = time.perf_counter_ns() - self.tel.epoch_ns
        self.done = True
        return self


class _SlowDevice(collections.deque):
    """The engine's queue of stores in flight, each entry replaced by a
    ``_Pending``."""

    def __init__(self, tel, log):
        super().__init__()
        self.tel, self.log = tel, log

    def append(self, _):
        super().append(_Pending(self.tel, self.log))


@pytest.mark.parametrize("name", ["tee_stream", "client", "tee"])
def test_at_most_two_stores_unfinished(name):
    tel = Telemetry(record_spans=True)
    eng, log = _engine(name, tel), []
    eng._in_flight = _SlowDevice(tel, log)
    n = 2 * B + 1
    for i in range(n):
        _push(eng, i)
        assert sum(not p.done for p in log) <= 2
    assert len(log) == n
    # every dispatch after the first two met an unfinished store
    assert tel.total("store_waits") == n - 2
    blocked = [p.blocked_at for p in log if p.blocked_at is not None]
    assert len(blocked) == n - 2
    stores = [sp for sp in tel.spans if sp.name == "store"]
    assert all(any(sp.t0_ns <= t <= sp.t0_ns + sp.dur_ns for sp in stores)
               for t in blocked)


@pytest.mark.parametrize("name", ["tee_stream", "tee"])
def test_finished_stores_are_not_counted_as_waits(name):
    tel = Telemetry(record_spans=False)
    eng, log = _engine(name, tel), []
    eng._in_flight = _SlowDevice(tel, log)
    for i in range(B + 2):
        for p in log:
            p.finish()
        _push(eng, i)
    assert tel.total("store_waits") == 0
    assert all(p.blocked_at is None for p in log)


def test_real_stores_bound_the_queue():
    """On the real arrays the queue never holds more than two stores, and
    a wait, when counted, is one per blocked dispatch."""
    tel = Telemetry(record_spans=False)
    eng = _engine("tee_stream", tel)
    for i in range(2 * B):
        _push(eng, i)
        assert len(eng._in_flight) <= 2
    assert 0 <= tel.total("store_waits") <= 2 * B - 2
