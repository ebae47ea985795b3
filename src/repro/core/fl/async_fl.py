"""Asynchronous FL (FedBuff / Papaya, the paper's ref [5]) — jitted engine.

The paper cites async FL as the optimization that cuts training time ~5x and
network overhead ~8x versus synchronous rounds.  This module provides:

  1. ``build_async_buffer_step`` — the jitted buffered-async aggregation
     step, built on the same unified engine (core/fl/aggregation.py) as the
     synchronous round: a stacked (buffer_size, D) device buffer of client
     deltas with their staleness values is staleness-weighted, DP-clipped,
     fixed-point secure-agg encoded, wraparound-summed, decoded and applied
     through the shared server optimizer in ONE batched on-device
     computation — no per-update host transfers.
  2. ``AsyncServer`` — the host facade: clients pull whatever model version
     is current and push deltas; pushes are written straight into a
     preallocated device buffer (one jitted dynamic-slot write, no float()
     round-trips), and the jitted step fires every ``buffer_size`` arrivals.
     Secure aggregation runs in-path (``mask_mode``): "client" makes the
     push write a MASKED int32 vector (clip/weight/encode/pairwise-mask in
     one jitted call) with dropout recovery at flush; "tee" fuses the mask
     lane into the Pallas accumulation kernel (bit-identical results).
  3. ``simulate`` — the event-driven fleet simulator (lognormal device
     times, dropouts) over a *numpy bytes model* for wall-clock/network
     accounting, and ``simulate_training`` — the same event loop driving the
     REAL jitted engines (sync ``round_step`` vs async buffer) end-to-end.
"""
from __future__ import annotations

import collections
import functools
import heapq
import math
from dataclasses import dataclass
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import telemetry as tele
from repro.core.fl import aggregation as agg
from repro.core.fl import compression as comp
from repro.core.fl import secure_agg as sa
from repro.core.fl.server_opt import build_server_opt
from repro.kernels.ops import on_tpu

# the PR 8 degradation-counter vocabulary, now telemetry-backed (the
# ``fault_metrics`` attribute is a deprecated dict view over these)
FAULT_METRIC_KEYS = ("duplicate_pushes", "rejected_pushes",
                     "subquorum_deferrals", "lost_contributions",
                     "released_updates")

# Stores that may be unfinished on the device when the host dispatches the
# next one.  The store donates the buffer, so no allocator wait for a buffer
# copy paces the host any more: unbounded, it would dispatch a whole session
# ahead of the device, and a session's last arrival would wait behind that
# backlog.  Two keeps one write running and one queued, so the device never
# waits on the host.  A property of the store, not a deployment setting.
_ROWS_IN_FLIGHT = 2


def batch_count(delta, params) -> Optional[int]:
    """None if ``delta`` is a single model update, else its leading-axis size.

    The unified ``push``/``encode_push`` API accepts either a pytree shaped
    exactly like the model or a STACKED batch of them (every leaf carrying
    one extra leading axis of a common size K).  Anything else is an error —
    ambiguity here would silently mis-aggregate.
    """
    p = jax.tree.leaves(params)
    d = jax.tree.leaves(delta)
    if len(p) != len(d):
        raise ValueError(
            f"delta has {len(d)} leaves, the model has {len(p)}")
    if all(tuple(x.shape) == tuple(y.shape) for x, y in zip(d, p)):
        return None
    if all(jnp.ndim(x) == jnp.ndim(y) + 1
           and tuple(jnp.shape(x)[1:]) == tuple(y.shape)
           for x, y in zip(d, p)):
        sizes = {jnp.shape(x)[0] for x in d}
        if len(sizes) == 1:
            return sizes.pop()
    raise ValueError(
        "delta leaves match neither the model's shapes nor a stacked "
        "(K, ...) batch of them")


def _write_rows(bufs, rows, slot):
    """Each chunk's row into its buffer at ``slot``: an in-bounds update
    (no out-of-range guard; the host validates the slot), which aliases a
    donated buffer instead of copying it."""
    return tuple(lax.dynamic_update_index_in_dim(b, r.astype(b.dtype), slot, 0)
                 for b, r in zip(bufs, rows))


def staleness_weight(staleness, mode: str = "polynomial", a: float = 0.5):
    """FedBuff staleness discounting: w = 1/(1+s)^a.

    Staleness is clamped at 0: a buggy/malicious client claiming a *future*
    model version must not inject NaN weights into the aggregate.
    """
    s = jnp.maximum(jnp.asarray(staleness, jnp.float32), 0.0)
    if mode == "constant":
        return jnp.ones_like(s)
    return (1.0 + s) ** (-a)


# ---------------------------------------------------------------------------
# The jitted buffered-async step
# ---------------------------------------------------------------------------
def build_async_buffer_step(params, fl_cfg, *, buffer_size: int,
                            staleness_mode: str = "polynomial",
                            staleness_exponent: float = 0.5,
                            mask_mode: str = "off",
                            use_pallas: Optional[bool] = None) -> Callable:
    """Returns jitted ``step(params, opt_state, buf, staleness, valid, rng)``.

    buf:       the raw client-delta buffer — a tuple of per-chunk
               (buffer_size, padded_c) f32 arrays laid out by the model's
               :class:`aggregation.ParamPlan` (``fl_cfg.param_chunk_elems``).
               A bare (buffer_size, D) array is accepted for the degenerate
               single-chunk plan (the legacy flat engine, bit-identical).
    staleness: (buffer_size,) f32 — server_version - pulled_version per slot.
    valid:     (buffer_size,) f32 — 1.0 for filled slots (partial flushes).

    mask_mode="tee" adds per-slot pairwise session masks to the encoded rows
    inside the fused aggregation (the paper's in-enclave protocol: all
    ``buffer_size`` masks are generated and cancelled within the trusted
    computation, so the result is bit-identical to mask_mode="off" while
    unmasked encodings never materialize in HBM).  For client-side masking
    with dropout recovery see ``build_masked_async_buffer_step``.

    The step shares clip / noise-placement / fixed-point encode / decode /
    server-optimizer semantics with the sync round via AggregationSpec: at
    staleness 0 with constant weighting it computes exactly the sync round's
    mean delta (up to fixed-point stochastic rounding).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if mask_mode not in ("off", "tee"):
        raise ValueError(f"mask_mode {mask_mode!r}: expected 'off' or 'tee'")
    spec = agg.make_spec(fl_cfg, buffer_size)
    if mask_mode == "tee" and not spec.use_secure_agg:
        raise ValueError("mask_mode='tee' requires secure_agg_bits > 0")
    if not spec.compression.identity:
        raise ValueError(
            f"upload compression ({spec.compression.describe()}) runs on "
            "the STREAMING engines only (mask_mode 'client'/'tee_stream' "
            "or the streamed 'off' encode): the batched buffer step holds "
            "raw f32 deltas, so there is no client-side wire to compress. "
            "Set compress_rate=1.0 here or switch to a streaming mode.")
    server = build_server_opt(fl_cfg)
    plan = agg.plan_for(params, fl_cfg)

    def step(params, opt_state, buf, staleness, valid, rng):
        bufs = buf if isinstance(buf, (tuple, list)) else (buf,)
        w = staleness_weight(staleness, staleness_mode, staleness_exponent)
        w = w * valid  # empty slots contribute nothing
        skey = jax.random.fold_in(rng, 0x7EE) if mask_mode == "tee" else None
        sessions = agg.plan_sessions(spec, plan, skey)
        mean_delta, stats = agg.aggregate_plan_buffer(
            bufs, w, spec, plan, rng, sessions=sessions,
            use_pallas=use_pallas)
        new_params, new_opt = server.apply(params, opt_state, mean_delta)
        metrics = dict(stats, staleness_mean=agg.ordered_sum(
            staleness * valid) / jnp.maximum(agg.ordered_sum(valid), 1.0))
        return new_params, new_opt, metrics

    return jax.jit(step)


def build_masked_async_buffer_step(params, fl_cfg, *, buffer_size: int,
                                   recover: bool = True,
                                   masked: bool = True) -> Callable:
    """The server half of the streamed buffered-async protocols.

    Returns jitted ``step(params, opt_state, mbuf, present, weights,
    staleness, norms, clips, session_key, rng)`` where ``mbuf`` is the
    **int32** buffer of masked fixed-point contributions written by
    ``AsyncServer.push`` (mask_mode="client") — a tuple of per-chunk
    (buffer_size, padded_c) arrays laid out by the model's
    :class:`aggregation.ParamPlan` (a bare (buffer_size, D) array is the
    degenerate single-chunk form) — the server never holds a raw delta.
    Each chunk runs its own mask session (key folded per chunk from
    ``session_key``); recovery sweeps per chunk.  ``present`` gates delivered slots; absent slots
    (dropouts / partial flushes) get their un-cancelled mask shares re-added
    inside the same jitted computation (``recovery_mask``), so the modular
    sum decodes to the exact survivor aggregate.  ``weights`` / ``norms`` /
    ``clips`` are the client-reported per-slot scalars used only for
    normalization and metrics.

    ``recover=False`` builds the steady-state variant for sessions the host
    KNOWS are complete (every slot delivered): the recovery sweep is elided
    entirely — the full session's pairwise masks cancel in the plain
    modular sum, bit-identically — so the common-case apply costs no PRF
    work at all.  ``AsyncServer`` uses it for every full-buffer apply and
    keeps the recovering variant for partial flushes.

    ``masked=False`` is the STREAMED-UNMASKED flush (the mask_mode="off"
    engine streaming its encode per arrival): same int32 buffer and
    present-gating, but there are no mask shares to recover — a partial
    flush is just the gated modular sum.
    """
    spec = agg.make_spec(fl_cfg, buffer_size)
    if not spec.use_secure_agg:
        raise ValueError("client-masked aggregation requires secure_agg_bits > 0")
    server = build_server_opt(fl_cfg)
    plan = agg.plan_for(params, fl_cfg)

    def step(params, opt_state, mbuf, present, weights, staleness, norms,
             clips, session_key, rng):
        mbufs = mbuf if isinstance(mbuf, (tuple, list)) else (mbuf,)
        w = weights * present
        metrics = agg.slot_metrics(w, norms, clips)
        w_total = metrics["weight_total"]
        sessions = agg.plan_sessions(spec, plan, session_key) if masked \
            else None
        # compressed-wire decode: re-derive the session's operators from
        # the SAME key the clients encoded against (None when identity)
        ops = agg.plan_operators(spec, plan, session_key)
        mean_delta = agg.aggregate_plan_masked_buffer(
            mbufs, present, w_total, spec, plan, sessions, rng,
            recover=recover, masked=masked, ops=ops)
        with jax.named_scope("apply"):
            new_params, new_opt = server.apply(params, opt_state, mean_delta)
        metrics["staleness_mean"] = agg.ordered_sum(staleness * present) \
            / jnp.maximum(agg.ordered_sum(present), 1.0)
        return new_params, new_opt, metrics

    return jax.jit(step)


class ClientPush(NamedTuple):
    """A client-side encoded push: what actually travels to the server in
    mask_mode="client" — the masked row in WIRE FORMAT plus the scalar
    metadata that rides the same channel.  ``version``/``slot`` pin the
    pairwise session and position the encoding was produced for."""

    # masked fixed-point encoding, bit-packed: the session's canonical
    # field residues ride as a dense uint32 word stream
    # (``secure_agg.pack_residues`` — ``ceil(log2(modulus))`` bits per
    # element, so a sub-32-bit field ships fewer bytes than the int32
    # row).  A (W,) uint32 array under the single-chunk plan, a tuple of
    # per-chunk word streams under a multi-chunk ParamPlan (one mask
    # session per chunk, same slot; every chunk shares the engine field).
    row: Any
    weight: jnp.ndarray  # staleness weight the client applied pre-encode
    norm: jnp.ndarray  # pre-clip L2 norm (client-side metric)
    clipped: jnp.ndarray  # 1.0 if the clip bound was active
    staleness: float
    version: int  # session id (server version at encode time)
    slot: int  # session position the mask was generated for
    # the field the residues were reduced into — the server rejects a push
    # whose wire width does not match its session field
    modulus: int = 1 << 32
    # per-push generation token (monotonic, assigned at encode time): the
    # server remembers delivered tokens, so a retried / duplicated /
    # replayed ClientPush is an idempotent no-op instead of a double-count.
    # 0 = untokened (hand-built pushes keep the strict legacy semantics).
    token: int = 0
    # the upload-compression spec the row was encoded under: the server
    # rejects a push whose sketch domain does not match its session's
    # (the identity spec == today's uncompressed packed wire)
    compression: comp.CompressionSpec = comp.CompressionSpec()


class AsyncServer:
    """Buffered asynchronous aggregation with staleness weighting + DP.

    The facade keeps only host metadata (version counter, fill pointer) in
    Python; every push is a single jitted write of the flattened delta into a
    preallocated (buffer_size, D) device buffer, and every apply is one
    invocation of the jitted buffer step.  No per-push host-device transfer
    of update payloads, no ``float()`` round-trips.

    mask_mode:
      "off"        — no masks.  With a secure-agg field configured the
                     engine STREAMS its encode per arrival exactly like
                     "tee_stream" (one jitted clip/weight/encode push into
                     an int32 buffer; the flush is a plain modular sum —
                     near-free), because the tee_stream restructuring
                     showed the batched flush was paying the whole encode
                     on the round's critical path for nothing.
                     ``stream_encode=False`` (or ``secure_agg_bits=0``)
                     falls back to the PR 1 batched engine: raw f32
                     buffer, server-side clip/encode at flush time.
      "tee"        — raw f32 buffer; the jitted step adds pairwise session
                     masks inside the fused in-enclave aggregation
                     (bit-identical results; with the Pallas path the masks
                     are generated in-kernel from PRF counters and never
                     exist in HBM).  The whole mask lane runs in the
                     batched apply, i.e. on the round's critical path.
      "tee_stream" — STREAMING in-enclave masking: the TEE runs the
                     clip/weight/encode/PRF-mask pipeline per arriving
                     delta (one jitted push), so the raw update never
                     rests in HBM at all — the buffer only ever holds
                     masked int32 rows — and the flush is a plain modular
                     sum (masks provably cancel).  Per-arrival mask work is
                     amortized into the gaps between arrivals instead of
                     stacking up at flush time.  Parity with "off" is to
                     stochastic-rounding tolerance (independent draws).
      "client"     — the buffer holds MASKED int32 vectors.  The protocol
                     is split along the real trust boundary:
                     ``encode_push`` is the CLIENT half (clip ->
                     staleness-weight -> stochastic fixed-point encode ->
                     pairwise PRF mask, one jitted call — in a fleet it
                     runs on the device, in parallel across clients), and
                     ``push_encoded`` is the SERVER half (a plain row
                     write; the server never sees an unmasked delta).  One
                     session per buffer round (session id = server
                     version).  Partial flushes (dropouts) re-add the
                     absent slots' mask shares inside the jitted step —
                     dropout recovery — so the decode is exact over the
                     survivors; full buffers skip recovery entirely (masks
                     provably cancel).  ``push(delta, ...)`` remains the
                     convenience wrapper that runs both halves back to
                     back.
    """

    def __init__(self, params, fl_cfg, buffer_size: int = 10,
                 staleness_exponent: float = 0.5,
                 staleness_mode: str = "polynomial",
                 mask_mode: str = "off",
                 session_seed: int = 0x5A5E,
                 use_pallas: Optional[bool] = None,
                 stream_encode: Optional[bool] = None,
                 strict: bool = True,
                 telemetry: Optional["tele.Telemetry"] = None):
        if mask_mode not in ("off", "tee", "tee_stream", "client"):
            raise ValueError(f"mask_mode {mask_mode!r}")
        self.params = params
        self.fl_cfg = fl_cfg
        self.buffer_size = buffer_size
        self.staleness_exponent = staleness_exponent
        self.staleness_mode = staleness_mode
        self.mask_mode = mask_mode
        self.version = 0
        self.last_metrics: Optional[dict] = None
        self._applied_updates = 0
        self._fill = 0
        # fault tolerance: strict=True raises on protocol violations (stale
        # session / conflicting slot — the debugging default); strict=False
        # counts-and-drops them so an unreliable fleet degrades instead of
        # crashing the aggregator.  Duplicate deliveries of a TOKENED push
        # are an idempotent no-op in both modes.
        self.strict = strict
        self.flush_quorum = float(getattr(fl_cfg, "flush_quorum", 0.0))
        # one registry for every counter/span the engine emits; the eid is
        # an EPHEMERAL random id (never a device/user identifier) keeping
        # this instance's series separate in a shared registry
        self.telemetry = (telemetry if telemetry is not None
                          else tele.get_default())
        self._eid = tele.new_session_id()
        self._tl = {"engine": "async", "eid": self._eid}
        # deprecated PR 8 spelling: a dict view over the registry counters
        self.fault_metrics = tele.TelemetryCounterView(
            self.telemetry, FAULT_METRIC_KEYS, **self._tl)
        self._token_counter = 0
        self._delivered_tokens: set = set()
        # per-slot presence (host metadata) — shared by every ingest path so
        # reordered / pinned-slot arrivals land correctly
        self._present = [False] * buffer_size
        # an undonated output of each store still in flight (oldest first)
        self._in_flight: collections.deque = collections.deque()
        self._session_base = jax.random.PRNGKey(session_seed)
        self._push_base = jax.random.PRNGKey(0xA5)
        if use_pallas is None:
            use_pallas = on_tpu()

        self._plan = agg.plan_for(params, fl_cfg)
        self._opt_state = build_server_opt(fl_cfg).init(params)
        self._stal = jnp.zeros((buffer_size,), jnp.float32)
        self._valid = jnp.zeros((buffer_size,), jnp.float32)

        spec = agg.make_spec(fl_cfg, buffer_size)
        self._spec = spec
        # enclave quantized wire: tee modes can ship packed sub-32-bit
        # words instead of the raw f32 delta (FLConfig.enclave_wire_bits)
        ebits = int(getattr(fl_cfg, "enclave_wire_bits", 0))
        self._enclave_bits = ebits if mask_mode in ("tee", "tee_stream") \
            else 0
        if self._enclave_bits:
            emod = (1 << ebits) if ebits < 32 else (1 << 32)
            evr = float(fl_cfg.secure_agg_range)
            eplan = self._plan

            @jax.jit
            def _enclave_wire(delta, rng):
                """CLIENT-side jit: stochastic quantize -> canonical field
                residues -> packed uint32 words (the actual wire) ->
                enclave-side unpack -> dequantize.  No f32 delta crosses
                the wire; the enclave ingests the quantized reconstruction.
                """
                xs = eplan.chunk_arrays(delta)
                keys = jax.random.split(rng, len(xs))
                outs, words = [], []
                for x, k in zip(xs, keys):
                    q = sa.quantize(x, ebits, evr, k)
                    w = sa.pack_residues(sa.to_field(q, emod), emod)
                    q2 = sa.recenter(
                        sa.unpack_residues(w, x.shape[-1], emod), emod)
                    outs.append(sa.dequantize(q2, ebits, evr))
                    words.append(w)
                return eplan.unchunk(tuple(outs)), tuple(words)

            self._enclave_wire = _enclave_wire
            self._enclave_seq = 0
            self._enclave_base = jax.random.PRNGKey(0xE7C)
        if mask_mode == "off":
            # the baseline engine streams its encode too (when it has an
            # integer field to stream into) — flush becomes near-free
            if stream_encode and not spec.use_secure_agg:
                raise ValueError(
                    "stream_encode requires secure_agg_bits > 0 (there is "
                    "no fixed-point field to stream the encode into)")
            streaming = (spec.use_secure_agg if stream_encode is None
                         else stream_encode)
        else:
            streaming = mask_mode in ("client", "tee_stream")
        self._streaming = streaming

        plan = self._plan
        if streaming:
            if not spec.use_secure_agg:
                raise ValueError(
                    f"mask_mode={mask_mode!r} requires secure_agg_bits > 0")
            masked = mask_mode != "off"
            # buffers live at the WIRE widths: under an active compression
            # spec every slot stores the compressed (sketch-domain) row
            wire = agg.plan_wire_chunks(spec, plan)
            # donated to every store: see ``_buf``
            self._bufs = tuple(jnp.zeros((buffer_size, wc.padded), jnp.int32)
                               for wc in wire)
            self._wts = jnp.zeros((buffer_size,), jnp.float32)
            self._norms = jnp.zeros((buffer_size,), jnp.float32)
            self._clips = jnp.zeros((buffer_size,), jnp.float32)
            # steady state: full sessions skip the recovery sweep entirely
            # (masks provably cancel); the recovering flush variant is
            # compiled lazily on the first partial flush (capturing self,
            # not the init-time params pytree, so nothing stale is pinned)
            self._step = build_masked_async_buffer_step(
                params, fl_cfg, buffer_size=buffer_size, recover=False,
                masked=masked)
            self._flush_step: Optional[Callable] = None
            self._build_flush_step = lambda: build_masked_async_buffer_step(
                self.params, fl_cfg, buffer_size=buffer_size, recover=True,
                masked=masked)
            s_mode, s_exp = staleness_mode, staleness_exponent

            @jax.jit
            def _masked_encode(delta, slot, s, session_key, rng):
                """The streamed-push encode pipeline (one jitted call).

                Runs on the device in mask_mode="client"; inside the
                enclave, per arriving delta, in mask_mode="tee_stream";
                and server-side (no mask) for the streamed "off" engine.
                Pytree-native: the delta is chunked per the ParamPlan,
                clipped by its whole-model norm, and each chunk is encoded
                against its own mask session — the full (D,) concatenation
                is never formed.
                """
                with jax.named_scope("encode"):
                    w = staleness_weight(s, s_mode, s_exp)
                    sessions = (agg.plan_sessions(spec, plan, session_key)
                                if masked else None)
                    ops = agg.plan_operators(spec, plan, session_key)
                    rows, nrm, clipped = agg.encode_plan_contribution(
                        delta, w, slot, spec, plan, sessions, rng,
                        masked=masked, use_pallas=use_pallas, ops=ops)
                return rows, w, nrm, clipped

            @functools.partial(jax.jit, donate_argnums=(0,))
            def _write_row(bufs, stal, wts, norms, clips, slot, rows, s, w,
                           nrm, clipped):
                """SERVER-side jit: store one masked row (all chunks) in
                place — the buffer is donated and the row written in bounds
                (the host validated the slot), so nothing is copied."""
                with jax.named_scope("store"):
                    return (_write_rows(bufs, rows, slot),
                            stal.at[slot].set(jnp.asarray(s, jnp.float32)),
                            wts.at[slot].set(w),
                            norms.at[slot].set(nrm),
                            clips.at[slot].set(clipped))

            @jax.jit
            def _wire_pack(rows, session_key):
                """CLIENT-side jit: rows -> wire format.  Each chunk's
                session ``reduce``s its row — canonical field residues,
                bit-packed into the dense uint32 stream the ClientPush
                actually ships (``session.modulus`` decides the width)."""
                sessions = agg.plan_sessions(spec, plan, session_key)
                return tuple(sess.reduce(r)
                             for sess, r in zip(sessions, rows))

            @jax.jit
            def _wire_unpack(wrows):
                """SERVER-side jit: packed wire words back to the int32
                residue rows the modular-sum buffer stores."""
                return tuple(
                    sa.unpack_residues(wr, wc.padded, spec.field_modulus)
                    for wr, wc in zip(wrows, wire))

            self._masked_encode = _masked_encode
            self._write_row = _write_row
            self._wire_pack = _wire_pack
            self._wire_unpack = _wire_unpack
        else:
            # donated to every store: see ``_buf``
            self._bufs = tuple(
                jnp.zeros((buffer_size, ck.padded), jnp.float32)
                for ck in plan.chunks)
            self._step = build_async_buffer_step(
                params, fl_cfg, buffer_size=buffer_size,
                staleness_mode=staleness_mode,
                staleness_exponent=staleness_exponent,
                mask_mode=mask_mode, use_pallas=use_pallas)

            @functools.partial(jax.jit, donate_argnums=(0,))
            def _write(bufs, stal, valid, slot, delta, s):
                """Store one raw delta's rows in place (as ``_write_row``)."""
                with jax.named_scope("store"):
                    rows = plan.chunk_arrays(delta, pad=True)
                    return (_write_rows(bufs, rows, slot),
                            stal.at[slot].set(jnp.asarray(s, jnp.float32)),
                            valid.at[slot].set(1.0))

            self._write = _write

    @property
    def plan(self) -> "agg.ParamPlan":
        """The model's chunk layout (``fl_cfg.param_chunk_elems``)."""
        return self._plan

    @property
    def _buf(self):
        """The contribution buffer — bare (B, D) array under the degenerate
        single-chunk plan (the legacy view), tuple of per-chunk arrays
        otherwise.  The next store donates it: read it between pushes, and
        keep no reference to it across a ``push``."""
        return self._bufs[0] if len(self._bufs) == 1 else self._bufs

    def _session_key(self):
        """PRNG key of the current pairwise-mask session (= buffer round).

        Multi-chunk plans fold one sub-key per chunk from this
        (``ParamPlan.session_keys``); the single-chunk plan uses it
        verbatim."""
        return jax.random.fold_in(self._session_base, self.version)

    def _new_token(self) -> int:
        self._token_counter += 1
        return self._token_counter

    def _span(self, name: str, **labels):
        """Engine span: labeled with the ephemeral eid and the session."""
        return self.telemetry.span(name, round=self.version, **self._tl,
                                   **labels)

    def open_slots(self) -> List[int]:
        """Session positions still awaiting a contribution."""
        return [i for i, p in enumerate(self._present) if not p]

    # -- client protocol ----------------------------------------------------
    def pull(self) -> Tuple[Any, int]:
        return self.params, self.version

    def encode_push(self, delta, client_version: int, rng=None,
                    slot: Optional[int] = None) -> ClientPush:
        """The CLIENT half of mask_mode='client': encode + mask one delta.

        Pure with respect to server state (reads only the current session
        id and the target slot) — in a real fleet this computation runs on
        the device, concurrently across clients; the server receives
        nothing but the returned ``ClientPush``.  ``slot`` defaults to the
        next free slot; concurrent clients of one session encode against
        the distinct slots the server assigned them at check-in.

        A STACKED delta (every leaf carrying one extra leading axis of a
        common size K) encodes K independent pushes against the next K free
        slots (or the K slots passed as ``slot``) and returns a list of
        ``ClientPush`` — the batched form of the unified API.
        """
        if self.mask_mode != "client":
            raise ValueError(
                f"encode_push is the client half of mask_mode='client' "
                f"(server is in mask_mode={self.mask_mode!r})")
        k = batch_count(delta, self.params)
        if k is not None:
            if slot is None:
                free = [i for i, p in enumerate(self._present) if not p]
                slots = free[:k]
            elif jnp.ndim(slot) == 0:
                # a scalar slot with a stacked batch broadcasts to the K
                # consecutive slots starting there
                s0 = int(slot)
                if s0 < 0 or s0 + k > self.buffer_size:
                    raise ValueError(
                        f"scalar slot={s0} with a stacked batch of {k} "
                        f"rows names session slots {s0}..{s0 + k - 1}, "
                        f"outside the session's {self.buffer_size} slots; "
                        f"pass an explicit slot sequence or start lower")
                slots = list(range(s0, s0 + k))
            else:
                slots = [int(s) for s in slot]
            if len(slots) < k:
                raise ValueError(
                    f"batched encode_push of {k} rows but only "
                    f"{len(slots)} session slots available")
            return [
                self.encode_push(jax.tree.map(lambda x: x[i], delta),
                                 client_version, rng, slots[i])
                for i in range(k)
            ]
        staleness = self.version - client_version  # host-int metadata only
        if slot is None:
            slot = self._present.index(False)  # lowest unfilled slot
        with self._span("encode_push", slot=slot) as sp:
            rows, w, nrm, clipped = self._encode_for_slot(delta, staleness,
                                                          slot, rng)
            # wire format: the packed residue stream is what travels
            rows = self._wire_pack(rows, self._session_key())
            sp.fence(rows)
        self.telemetry.count(
            "upload_bytes", 4 * sum(int(r.size) for r in rows),
            lane=("packed" if self._spec.compression.identity
                  else "compressed"), **self._tl)
        row = rows[0] if len(rows) == 1 else rows
        return ClientPush(row, w, nrm, clipped, staleness, self.version,
                          slot, self._spec.field_modulus, self._new_token(),
                          self._spec.compression)

    def _encode_for_slot(self, delta, staleness, slot: int, rng=None):
        """One masked encode bound to (current session, ``slot``): the
        ``encode`` span covers the session key, the per-slot rng and the
        encode's dispatch."""
        with self._span("encode", slot=slot):
            if rng is None:
                rng = jax.random.fold_in(
                    jax.random.fold_in(self._push_base, self.version), slot)
            return self._masked_encode(delta, slot, staleness,
                                       self._session_key(), rng)

    def push_encoded(self, cp: ClientPush, rng=None):
        """The SERVER half of mask_mode='client': store one masked row.

        Arrivals may land in any order — each ``ClientPush`` carries the
        slot its mask was generated for.  A TOKENED push that was already
        delivered (a retry or wire-level duplicate) is an idempotent no-op
        (counted, never double-stored).  A push whose session has already
        been applied (the pairwise masks of a new session no longer cancel
        against it) or whose slot conflicts with a different delivered
        push is rejected: ``strict=True`` raises, ``strict=False``
        counts-and-drops (``fault_metrics['rejected_pushes']``).  Returns
        True when the row was stored.  A list of pushes (the batched
        ``encode_push`` form) is stored row by row (returns the count).
        """
        if self.mask_mode != "client":
            raise ValueError(
                f"push_encoded is the server half of mask_mode='client' "
                f"(server is in mask_mode={self.mask_mode!r})")
        if isinstance(cp, list):
            return sum(1 for one in cp if self.push_encoded(one, rng))
        with self._span("push_encoded", slot=cp.slot):
            return self._push_encoded_one(cp, rng)

    def _push_encoded_one(self, cp: ClientPush, rng=None) -> bool:
        if cp.token and cp.token in self._delivered_tokens:
            self.fault_metrics["duplicate_pushes"] += 1
            return False
        if (cp.version != self.version or not 0 <= cp.slot < self.buffer_size
                or self._present[cp.slot]):
            if not self.strict:
                self.fault_metrics["rejected_pushes"] += 1
                return False
            raise ValueError(
                f"stale ClientPush (session {cp.version} slot {cp.slot}; "
                f"server at session {self.version}, slot filled="
                f"{self._present[cp.slot] if 0 <= cp.slot < self.buffer_size else 'n/a'}): "
                "the pairwise mask no longer matches an open session position")
        if cp.modulus != self._spec.field_modulus:
            raise ValueError(
                f"ClientPush packed for field modulus {cp.modulus} "
                f"({sa.wire_bits(cp.modulus)}-bit wire) but the server's "
                f"session field is {self._spec.field_modulus} "
                f"({sa.wire_bits(self._spec.field_modulus)}-bit): the "
                "residue stream cannot be unpacked — client and server must "
                "agree on secure_agg_bits and the session size")
        if cp.compression != self._spec.compression:
            raise ValueError(
                f"ClientPush encoded under compression "
                f"{cp.compression.describe()} but the server's session "
                f"expects {self._spec.compression.describe()}: the row "
                "lives in a different sketch domain and would decode to "
                "garbage — client and server must agree on compress_mode "
                "and compress_rate for the session")
        wrows = cp.row if isinstance(cp.row, tuple) else (cp.row,)
        self.telemetry.count(
            "upload_bytes", 4 * sum(int(w_.size) for w_ in wrows),
            lane=("packed" if self._spec.compression.identity
                  else "compressed"), **self._tl)
        rows = self._wire_unpack(wrows)  # back to int32 residue rows
        if cp.token:
            self._delivered_tokens.add(cp.token)
        self._store_row(cp.slot, rows, cp.staleness, cp.weight, cp.norm,
                        cp.clipped, rng)
        return True

    def _store_row(self, slot: int, row, staleness, w, nrm, clipped,
                   rng=None) -> None:
        """Write one masked row into its session slot (+ apply when full).

        The ``store`` span covers the wait for a store in flight, the
        write's dispatch and the slot bookkeeping, and closes before the
        apply (``decode``) opens."""
        rows = row if isinstance(row, tuple) else (row,)
        with self._span("store", slot=slot):
            self._bound_in_flight()
            (self._bufs, self._stal, self._wts, self._norms,
             self._clips) = self._write_row(
                self._bufs, self._stal, self._wts, self._norms, self._clips,
                slot, rows, staleness, w, nrm, clipped)
            self._in_flight.append(self._stal)
            self._mark_stored(slot)
        if self._fill >= self.buffer_size:
            self._apply(rng)

    def _bound_in_flight(self) -> None:
        """Before a store: wait until fewer than ``_ROWS_IN_FLIGHT``
        earlier stores are unfinished, counting each dispatch that had to
        wait (``store_waits``)."""
        while len(self._in_flight) >= _ROWS_IN_FLIGHT:
            done = self._in_flight.popleft()
            if not done.is_ready():
                self.telemetry.count("store_waits", **self._tl)
                done.block_until_ready()

    def _mark_stored(self, slot: int) -> None:
        self._present[slot] = True
        self._fill += 1
        self.telemetry.count("stored_contributions", **self._tl)
        self.telemetry.gauge("buffered_contributions", self._fill,
                             **self._tl)

    def push(self, delta, client_version: int, rng=None,
             slot: Optional[int] = None, push_id: Optional[int] = None):
        """Push one model delta — or a STACKED batch of them.

        The one entry point of the unified pytree API: ``delta`` is a
        pytree shaped like the model (one contribution) or a stacked
        (K, ...) batch (K contributions, stored in arrival order).  The
        engine routes it through whatever path the mask mode requires.

        ``slot`` pins the session position (default: lowest unfilled).
        Because per-slot PRF streams are keyed by (session, slot), pinned
        pushes are bit-reproducible however arrivals are ordered — the
        contract the fault-injection layer replays against.  ``push_id``
        is an optional idempotence token for raw pushes: a repeated id is
        a counted no-op (the retry/duplicate contract ``ClientPush.token``
        gives the encoded path).  Returns True when the contribution was
        stored.
        """
        k = batch_count(delta, self.params)
        if k is not None:
            slots = [None] * k if slot is None else list(slot)
            return sum(1 for i in range(k)
                       if self.push(jax.tree.map(lambda x: x[i], delta),
                                    client_version, rng, slot=slots[i]))
        with self._span("push", mode=self.mask_mode):
            return self._push_one(delta, client_version, rng, slot, push_id)

    def _push_one(self, delta, client_version: int, rng=None,
                  slot: Optional[int] = None,
                  push_id: Optional[int] = None) -> bool:
        if push_id is not None and push_id in self._delivered_tokens:
            self.fault_metrics["duplicate_pushes"] += 1
            return False
        if slot is not None:
            if not 0 <= slot < self.buffer_size or self._present[slot]:
                if not self.strict:
                    self.fault_metrics["rejected_pushes"] += 1
                    return False
                raise ValueError(
                    f"slot {slot} is not an open position of session "
                    f"{self.version}")
        if self.mask_mode == "client":
            ok = self.push_encoded(
                self.encode_push(delta, client_version, slot=slot), rng)
            if ok and push_id is not None:
                self._delivered_tokens.add(push_id)
            return ok
        staleness = self.version - client_version  # host-int metadata only
        if push_id is not None:
            self._delivered_tokens.add(push_id)
        if self._enclave_bits:
            # enclave quantized wire: the delta the tee ingests is the
            # client-side stochastic quantization's reconstruction; the
            # packed word streams are what actually crossed the wire
            ekey = jax.random.fold_in(self._enclave_base, self._enclave_seq)
            self._enclave_seq += 1
            delta, ewords = self._enclave_wire(delta, ekey)
            self.telemetry.count(
                "upload_bytes", 4 * sum(int(w_.size) for w_ in ewords),
                lane="enclave", **self._tl)
        if self._streaming:
            # streaming encode: process the arriving delta NOW (one jitted
            # call — in "tee_stream" masked, so the raw update never rests
            # in HBM; in streamed "off" plain) and leave the flush nothing
            # but the modular sum
            if slot is None:
                slot = self._present.index(False)  # lowest unfilled slot
            rows, w, nrm, clipped = self._encode_for_slot(delta, staleness,
                                                          slot)
            self._store_row(slot, rows, staleness, w, nrm, clipped, rng)
            return True
        if slot is None:
            slot = self._present.index(False)
        with self._span("store", slot=slot):
            self._bound_in_flight()
            self._bufs, self._stal, self._valid = self._write(
                self._bufs, self._stal, self._valid, slot, delta,
                staleness)
            self._in_flight.append(self._stal)
            self._mark_stored(slot)
        if self._fill >= self.buffer_size:
            self._apply(rng)
        return True

    def flush(self, rng=None, force: bool = False) -> bool:
        """Apply a partially-filled buffer (end of run / deadline).

        In mask_mode="client" this is the dropout-recovery path: the absent
        slots' pairwise-mask shares are reconstructed and cancelled inside
        the jitted step, exactly as surviving clients would supply them.

        A flush below ``FLConfig.flush_quorum`` (a fraction of the session's
        slots) ABSTAINS: nothing is decoded or applied, the buffered
        contributions stay in place for late arrivals, and
        ``fault_metrics['subquorum_deferrals']`` counts the deferral —
        the engine never releases a garbage sub-quorum aggregate.
        ``force=True`` overrides the quorum (operator intervention).
        Returns True when a params update was released.
        """
        if self._fill <= 0:
            return False
        with self._span("flush", forced=force, fill=self._fill):
            need = math.ceil(self.flush_quorum * self.buffer_size)
            if not force and self._fill < need:
                self.fault_metrics["subquorum_deferrals"] += 1
                return False
            self._apply(rng)
        return True

    # -- server step --------------------------------------------------------
    def _apply(self, rng=None) -> None:
        if rng is None:  # deterministic per-version stream for rounding/noise
            rng = jax.random.fold_in(jax.random.PRNGKey(0xA5), self.version)
        recovery = self._fill < self.buffer_size
        with self._span("decode", recovery=recovery, fill=self._fill) as sp:
            if self._streaming:
                present = jnp.asarray(
                    [1.0 if p else 0.0 for p in self._present], jnp.float32)
                if not recovery:
                    step = self._step  # complete session: no recovery needed
                else:
                    if self._flush_step is None:
                        self._flush_step = self._build_flush_step()
                    step = self._flush_step  # recovery for absent slots
                self.params, self._opt_state, self.last_metrics = step(
                    self.params, self._opt_state, self._bufs, present,
                    self._wts, self._stal, self._norms, self._clips,
                    self._session_key(), rng)
                self._present = [False] * self.buffer_size
            else:
                self.params, self._opt_state, self.last_metrics = self._step(
                    self.params, self._opt_state, self._bufs, self._stal,
                    self._valid, rng)
                self._valid = jnp.zeros_like(self._valid)
                self._present = [False] * self.buffer_size
            sp.fence(self.params)
        self.version += 1
        self._applied_updates += self._fill
        self.telemetry.count("aggregated_contributions", self._fill,
                             **self._tl)
        self.telemetry.gauge("buffered_contributions", 0, **self._tl)
        self._fill = 0
        self.fault_metrics["released_updates"] += 1


# ---------------------------------------------------------------------------
# Event-driven wall-clock / network simulation (sync vs async)
# ---------------------------------------------------------------------------
@dataclass
class SimResult:
    wall_clock: float
    bytes_up: float
    bytes_down: float
    applied_updates: int
    server_steps: int

    @property
    def total_bytes(self) -> float:
        return self.bytes_up + self.bytes_down


def _device_times(n: int, seed: int, mu: float = 2.5, sigma: float = 1.2):
    import numpy as np
    rs = np.random.RandomState(seed)
    return np.exp(rs.normal(mu, sigma, size=n))  # heavy-tailed local-train times


def simulate(mode: str, *, population: int, cohort: int, target_updates: int,
             model_bytes: float, seed: int = 0, dropout: float = 0.1,
             buffer_size: int = 10, over_select: float = 1.3,
             round_overhead: float = 30.0) -> SimResult:
    """Simulate until `target_updates` client updates are applied.

    sync: rounds select cohort*over_select devices, wait for the cohort-th
          fastest survivor (stragglers discarded — their upload is wasted)
          plus a fixed per-round coordination overhead (deploy/aggregate).
    async: devices stream continuously; server applies every buffer_size
          arrivals.  (Papaya's observed 5x / 8x gains come from exactly this
          straggler/over-selection/coordination waste.)
    """
    import numpy as np
    times = _device_times(population, seed)
    rs = np.random.RandomState(seed + 1)

    if mode == "sync":
        t, up, down, applied, steps = 0.0, 0.0, 0.0, 0, 0
        while applied < target_updates:
            n_sel = int(cohort * over_select)
            sel = rs.choice(population, size=n_sel, replace=False)
            alive = sel[rs.uniform(size=n_sel) > dropout]
            down += n_sel * model_bytes  # everyone selected downloads
            finish = np.sort(times[alive])
            if len(finish) < cohort:
                t += (float(finish[-1]) if len(finish) else 1.0) + round_overhead
                continue
            t += float(finish[cohort - 1]) + round_overhead
            up += len(alive) * model_bytes  # all survivors upload (late ones wasted)
            applied += cohort
            steps += 1
        return SimResult(t, up, down, applied, steps)

    if mode == "async":
        # each device loops: pull -> train -> push; concurrency = cohort
        heap: List[Tuple[float, int]] = []
        active = rs.choice(population, size=cohort, replace=False)
        for d in active:
            heapq.heappush(heap, (float(times[d]), int(d)))
        t, applied, steps = 0.0, 0, 0
        down = cohort * model_bytes
        up = 0.0
        buf = 0
        while applied < target_updates:
            t, d = heapq.heappop(heap)
            if rs.uniform() < dropout:
                pass  # dropped mid-training: no upload
            else:
                up += model_bytes
                buf += 1
                applied += 1
                if buf >= buffer_size:
                    buf = 0
                    steps += 1
            nxt = int(rs.randint(population))
            down += model_bytes
            heapq.heappush(heap, (t + float(times[nxt]), nxt))
        return SimResult(t, up, down, applied, steps)

    raise ValueError(mode)


# ---------------------------------------------------------------------------
# Event-driven simulation over the REAL jitted engines
# ---------------------------------------------------------------------------
@dataclass
class TrainingSimResult:
    sim: SimResult
    losses: List[float]  # per-applied-update client loss trace
    host_seconds: float  # real wall-clock spent in the jitted engines
    killed: int = 0  # devices that died mid-round (their work is wasted)
    released_updates: int = 0  # server applies that released a params update
    wasted_updates: int = 0  # trained contributions never released
    fault_metrics: Optional[dict] = None  # the engine's degradation counters

    @property
    def final_loss(self) -> float:
        import numpy as np
        k = max(1, len(self.losses) // 10)
        return float(np.mean(self.losses[-k:]))

    def steps_to_loss(self, target: float) -> Optional[int]:
        """First applied update whose trailing-10 mean loss hits ``target``
        (None if never reached) — the convergence metric bench_churn sweeps."""
        import numpy as np
        xs = np.asarray(self.losses, np.float64)
        for i in range(len(xs)):
            lo = max(0, i - 9)
            if float(xs[lo:i + 1].mean()) <= target:
                return i + 1
        return None


def simulate_training(mode: str, *, loss_fn: Callable, params, fl_cfg,
                      make_client_batch: Callable, target_updates: int,
                      cohort: int, population: int = 1024,
                      buffer_size: int = 10, model_bytes: float = 4e6,
                      seed: int = 0, dropout: float = 0.0,
                      dropout_rate: Optional[float] = None,
                      devices: Optional[Any] = None,
                      mask_mode: str = "off",
                      staleness_exponent: float = 0.5,
                      round_overhead: float = 30.0,
                      faults: Optional[Any] = None,
                      data_by_device: bool = False,
                      telemetry: Optional["tele.Telemetry"] = None
                      ) -> TrainingSimResult:
    """The event-driven fleet simulation driving the real jitted engines.

    mode="sync": the shared jitted ``round_step`` over cohort-sized rounds
    (wall-clock = straggler of each round + coordination overhead).
    mode="async": the heterogeneous-fleet event loop feeding the jitted
    ``async_buffer_step`` through ``AsyncServer`` — each completing device
    trained against the (stale) version it pulled.

    ``dropout_rate`` kills devices mid-round: in sync mode their weight is
    zeroed in the cohort batch; in async mode the trained update is never
    pushed, so with ``mask_mode="client"`` their pairwise-mask session slot
    stays empty and the final flush exercises the dropout-recovery path.
    (``dropout`` is the historical alias.)  When a
    ``repro.core.device_sim.DevicePopulation`` is passed as ``devices``, the
    per-device kill probability is modulated by its resource state
    (battery / wifi / churn) via ``device_sim.midround_dropout_prob``.

    ``mask_mode`` selects the secure-aggregation path of the async engine
    ("off" | "tee" | "tee_stream" | "client" — see ``AsyncServer``).

    ``make_client_batch(client_seed, n_clients)`` must return a batch pytree
    with leading axis ``n_clients``.  Simulated wall-clock uses the same
    lognormal device-time model as ``simulate``; ``host_seconds`` measures
    the actual jitted compute.

    When ``devices`` carries a :class:`~repro.core.device_sim.ChurnModel`
    the async loop steps the population's sticky churn once per server
    apply, draws the next arriving device availability-weighted (diurnal
    waves / charging+wifi bias), and uses each device's tiered speed as its
    round time — realistic heterogeneous-fleet arrivals.  (Without a churn
    model the legacy i.i.d. event process is bit-identical to before.)

    ``fl_cfg.fedprox_mu`` adds the proximal term to the local objective;
    ``fl_cfg.scaffold`` runs SCAFFOLD: the server model becomes the pytree
    ``{'x': params, 'c': control_variate}`` and each client pushes
    ``{'x': delta_x, 'c': delta_c * buffer_size / population}`` through the
    SAME pytree push API (masked modes included), so the variates ride the
    aggregation channel next to the model delta.  Async mode only.

    ``faults`` accepts a :class:`repro.core.fl.faults.FaultPlan` (the async
    server is wrapped in its :class:`~repro.core.fl.faults.FaultInjector`,
    and straggler tails stretch device times) — the chaos-testing hook.
    ``data_by_device=True`` keys each client batch by DEVICE id instead of
    the arrival counter: every device owns a fixed shard, i.e. the non-IID
    regime where drift correction (FedProx / SCAFFOLD) earns its keep.
    """
    import time as _time

    import numpy as np

    from repro.core.fl.round import build_client_update, build_round_step, \
        init_fl_state

    if dropout_rate is None:
        dropout_rate = dropout
    if getattr(fl_cfg, "scaffold", False) and mode != "async":
        raise ValueError(
            "FLConfig.scaffold=True is the buffered-async drift correction "
            "(control variates ride the async push API); use mode='async'")
    if devices is not None:
        from repro.core.device_sim import midround_dropout_prob
        assert len(devices) >= population

        def kill_prob(d: int) -> float:
            return midround_dropout_prob(devices.devices[d], dropout_rate)
    else:
        def kill_prob(d: int) -> float:
            return dropout_rate

    times = _device_times(population, seed)
    rs = np.random.RandomState(seed + 1)
    key = jax.random.PRNGKey(seed)
    losses: List[float] = []

    if mode == "sync":
        step = build_round_step(loss_fn, fl_cfg, cohort_size=cohort,
                                telemetry=telemetry)
        state = init_fl_state(params, fl_cfg)
        # dedicated kill stream: device selection (and every seeded result at
        # dropout_rate=0) stays bit-identical to the dropout-free engine
        rs_kill = np.random.RandomState(seed + 2)
        t, up, down, applied, steps = 0.0, 0.0, 0.0, 0, 0
        host0 = _time.perf_counter()
        while applied < target_updates:
            sel = rs.choice(population, size=cohort, replace=False)
            batch = dict(make_client_batch(steps, cohort))
            if dropout_rate > 0.0:
                survive = np.asarray(
                    [rs_kill.uniform() >= kill_prob(d) for d in sel],
                    np.float32)
                if survive.sum() == 0.0:
                    survive[0] = 1.0  # degenerate round: keep one survivor
                prior_w = batch.get("weight")
                batch["weight"] = (jnp.asarray(survive) if prior_w is None
                                   else jnp.asarray(survive) * prior_w)
            else:
                survive = np.ones((cohort,), np.float32)
            state, metrics = step(state, batch, jax.random.fold_in(key, steps))
            losses.append(float(metrics["loss"]))
            t += float(np.max(times[sel])) + round_overhead
            down += cohort * model_bytes
            up += int(survive.sum()) * model_bytes
            applied += int(survive.sum())
            steps += 1
        host = _time.perf_counter() - host0
        return TrainingSimResult(
            SimResult(t, up, down, applied, steps), losses, host)

    if mode == "async":
        scaffold = bool(getattr(fl_cfg, "scaffold", False))
        churn_on = (devices is not None
                    and getattr(devices, "churn", None) is not None)
        if scaffold:
            from repro.core.fl.round import build_scaffold_client_update
            zeros_c = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                   params)
            scaffold_update = jax.jit(
                build_scaffold_client_update(loss_fn, fl_cfg))
            c_scale = buffer_size / population  # the |S|/N server-variate rate
            ci: dict = {}  # device -> client control variate (lazy zeros)
            srv = AsyncServer({"x": params, "c": zeros_c}, fl_cfg,
                              buffer_size=buffer_size,
                              staleness_exponent=staleness_exponent,
                              mask_mode=mask_mode, telemetry=telemetry)
        else:
            client_update = jax.jit(build_client_update(loss_fn, fl_cfg))
            srv = AsyncServer(params, fl_cfg, buffer_size=buffer_size,
                              staleness_exponent=staleness_exponent,
                              mask_mode=mask_mode, telemetry=telemetry)
        eng = srv
        if faults is not None:
            from repro.core.fl.faults import FaultInjector
            eng = FaultInjector(srv, faults)

        def round_time(d: int) -> float:
            base = (float(devices.devices[d].speed) if churn_on
                    else float(times[d]))
            if faults is not None:
                base *= faults.straggler_mult(d)
            return base

        def next_device() -> int:
            if churn_on:
                w = np.asarray([devices.availability_weight(devices.devices[i])
                                for i in range(population)], np.float64)
                tot = w.sum()
                if tot > 0.0:
                    return int(rs.choice(population, p=w / tot))
            return int(rs.randint(population))

        # in-flight: (finish_time, device, client_seed, (version, params) at
        # PULL time — the device really trains against its stale snapshot
        # (cseed is unique, so heap comparison never reaches the pytree)
        heap: List[Tuple[float, int, int, Tuple[int, Any]]] = []
        for i, d in enumerate(rs.choice(population, size=cohort,
                                        replace=False)):
            params_now, ver_now = eng.pull()
            heapq.heappush(heap, (round_time(int(d)), int(d), i,
                                  (ver_now, params_now)))
        t, applied, n_started, killed = 0.0, 0, cohort, 0
        down, up = cohort * model_bytes, 0.0
        last_ver = srv.version
        host0 = _time.perf_counter()
        while applied < target_updates:
            t, d, cseed, (pulled_version, pulled_params) = heapq.heappop(heap)
            if rs.uniform() >= kill_prob(d):
                batch = make_client_batch(d if data_by_device else cseed, 1)
                cbatch = jax.tree.map(lambda x: x[0], batch)
                crng = jax.random.fold_in(key, cseed)
                if scaffold:
                    cc = ci.get(d)
                    if cc is None:
                        cc = zeros_c
                    (dx, dc), loss = scaffold_update(
                        pulled_params["x"], pulled_params["c"], cc, cbatch,
                        crng)
                    ci[d] = jax.tree.map(lambda a, b: a + b, cc, dc)
                    delta = {"x": dx,
                             "c": jax.tree.map(lambda v: v * c_scale, dc)}
                else:
                    delta, loss = client_update(pulled_params, cbatch, crng)
                eng.push(delta, pulled_version,
                         rng=jax.random.fold_in(key, 0x5000 + applied))
                losses.append(float(loss))
                up += model_bytes
                applied += 1
            else:
                killed += 1  # mid-round death: its local work is wasted
            if churn_on and srv.version != last_ver:
                devices.step()  # world time advances once per server apply
                last_ver = srv.version
            nxt = next_device()
            params_now, ver_now = eng.pull()
            heapq.heappush(heap, (t + round_time(nxt), nxt, n_started,
                                  (ver_now, params_now)))
            n_started += 1
            down += model_bytes
        # deadline flush: a partially-filled buffer is applied; in
        # mask_mode="client" the empty session slots go through dropout
        # recovery (their mask shares are cancelled inside the jitted step).
        # Below FLConfig.flush_quorum the flush ABSTAINS — the buffered
        # tail is never released as a garbage sub-quorum aggregate.
        eng.flush(rng=jax.random.fold_in(key, 0x6000))
        host = _time.perf_counter() - host0
        fm = dict(srv.fault_metrics)
        wasted = (killed + fm["rejected_pushes"] + fm["lost_contributions"]
                  + srv._fill)
        return TrainingSimResult(
            SimResult(t, up, down, applied, srv.version), losses, host,
            killed=killed, released_updates=fm["released_updates"],
            wasted_updates=wasted, fault_metrics=fm)

    raise ValueError(mode)
