"""Hierarchical aggregation tier — masked rounds across a device mesh.

The paper's production architecture scales FL by fanning clients out over
MANY aggregators that combine partial sums hierarchically before the main
aggregator applies the server step; a single host's buffer caps round size
otherwise.  Because masked secure aggregation is a MODULAR sum (int32
addition wraps mod 2^32, associative and commutative *exactly*), partial
sums commute across shards: any leaf/root tier preserves bit-exactness
while multiplying ingest and flush throughput.

Two session topologies share the tier's state layout (a device-resident
(num_leaves, leaf_buffer, D) buffer sharded over the "leaf" mesh axis):

**One sharded global session** (``two_level=False``, the PR 4 layout):
``num_leaves * leaf_buffer`` slots of ONE mask session; each leaf runs the
single-host row pipeline over its contiguous slot shard plus its shard of
the gated recovery edge sweep — recovery edges CROSS leaves, so a dropout
anywhere sweeps a partition of the whole session graph.

**A session tree** (``two_level=True``, the paper's tiered service): every
leaf runs its OWN local mask session over its ``leaf_buffer`` slots and
flushes a still-masked partial into a ROOT session over ``num_leaves``
slots:

                 clients ──► destination-sharded ingest (encode per leaf)
                     │
      ┌──────────────┼──────────────────┐
      ▼              ▼                  ▼
   leaf 0         leaf 1    ...      leaf L-1      (shard_map over "leaf";
   LOCAL session  LOCAL session      LOCAL session  several logical leaves
   over Bl slots  over Bl slots      over Bl slots  per device when
   gated Σ + own  gated Σ + own      gated Σ + own  L > device count)
   recovery       recovery           recovery
   + root mask[0] + root mask[1]     + root mask[L-1]   (root session,
      │              │                  │                L slots)
      └─────── field-modulus psum (int32, mod 2^32) ──────┐
                                                          ▼
                                root: + root recovery for DEAD leaves →
                                dequantize → weight-normalize →
                                central DP noise (once) → server opt

The tree is FAULT-ISOLATED: a client dropout inside leaf l is recovered by
sweeping only leaf l's local session edges (an O(Bl * k) sweep over the
leaf's own present vector — no global state), and a whole dead leaf is one
absent slot of the L-slot root session, recovered with a single root
sweep.  In the sharded-global-session layout the same dropout gates a
partition of an O(B * k) edge list on EVERY leaf against a replicated
(B,) present vector.  Decoded results are bit-identical either way — and
bit-identical to the single-host engines at
``buffer_size = num_leaves * leaf_buffer`` for all four mask modes
("off" streamed / "client" / "tee" / "tee_stream"), with and without
client and whole-leaf dropout — enforced by tests/test_hierarchy.py.

``ShardedAsyncServer`` is the facade.  Batched arrival ingestion is
DESTINATION-SHARDED: a (K,) batch of pushes is routed (a host-side index
shuffle, no row math) to its destination leaves and the
clip/weight/encode[+mask] pipeline runs INSIDE a shard_map, each leaf
encoding only the rows addressed to it — no central (K, D) encode precedes
the scatter, so ingest bandwidth scales with the leaf count.  Rows are
bit-identical to sequential single pushes (same per-slot PRF streams).
"""
from __future__ import annotations

import math
import warnings
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import telemetry as tele
from repro.core.fl import aggregation as agg
from repro.core.fl import secure_agg as sa
from repro.core.fl.async_fl import (FAULT_METRIC_KEYS, ClientPush,
                                    batch_count, staleness_weight)
from repro.core.fl.server_opt import build_server_opt
from repro.kernels.ops import on_tpu
from repro.launch.mesh import (LEAF_AXIS, leaves_per_device, make_agg_mesh,
                               make_leaf_mesh)
from repro.launch.sharding import hierarchy_shardings

# fold-in tags deriving the session tree's keys from one round key
# (disjoint from the 0x5E55/0x7EE/0xDEE engine stream tags and from
# secure_agg.GRAPH_PERM_TAG)
LEAF_SESSION_TAG = 0x1EAF
ROOT_SESSION_TAG = 0x4007


def leaf_session(spec, session_key, leaf, leaf_buffer: int) -> sa.MaskSession:
    """Leaf ``leaf``'s LOCAL mask session of the session tree.

    Keyed by (round session key, leaf index) — disjoint leaves draw
    disjoint pair streams (and, for random k-regular graphs, independent
    per-leaf permutations), which is exactly what makes the tree
    fault-isolated: no stream is shared across leaves, so no recovery
    sweep ever crosses a leaf boundary.  Traceable in ``leaf``.
    """
    key = jax.random.fold_in(
        jax.random.fold_in(session_key, LEAF_SESSION_TAG), leaf)
    return agg.make_mask_session(spec, key, num_slots=leaf_buffer)


def root_session(spec, session_key, num_leaves: int) -> sa.MaskSession:
    """The ROOT session over ``num_leaves`` slots: each alive leaf adds the
    mask of its root slot to the partial it flushes upward, so the root
    combine only ever sees masked leaf partials; a dead leaf is one absent
    root slot, recovered by a single ``num_leaves``-sized sweep."""
    return agg.make_mask_session(
        spec, jax.random.fold_in(session_key, ROOT_SESSION_TAG),
        num_slots=num_leaves)


def _partition_edges(session: sa.MaskSession, num_leaves: int):
    """Split the session mask graph's edge list into ``num_leaves`` shards.

    Returns (lo, hi, w) each (num_leaves * per_leaf,): equal-size chunks
    padded with weight-0 edges so every leaf sweeps an identically-shaped
    block.  Any partition of the edge set yields the same recovery term
    (int32 partial sums commute mod 2^32), so a flat split is exact.
    """
    lo, hi = session.edges()
    E = int(lo.shape[0])
    per = max(1, -(-E // num_leaves))
    pad = num_leaves * per - E
    w = jnp.concatenate([jnp.ones((E,), jnp.int32),
                         jnp.zeros((pad,), jnp.int32)])
    lo = jnp.concatenate([lo, jnp.zeros((pad,), jnp.int32)])
    hi = jnp.concatenate([hi, jnp.zeros((pad,), jnp.int32)])
    return lo, hi, w


def _pad_to(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad a chunk-sized vector up to its storage width (no-op for the
    single-chunk plan, whose storage is unpadded)."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, (0, width - x.shape[-1]))


def _take_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[idx]`` for a short traced row index, one dynamic slice per row.

    The same rows as ``jnp.take(x, idx, axis=0)``; as one XLA gather of
    model-width rows it costs the TPU compiler close to a minute.
    """
    return jnp.stack([jax.lax.dynamic_index_in_dim(x, idx[j], keepdims=False)
                      for j in range(idx.shape[0])])


def _as_chunks(buf) -> tuple:
    """Normalize a buffer argument to the plan's per-chunk tuple — a bare
    array is the degenerate single-chunk layout."""
    return tuple(buf) if isinstance(buf, (tuple, list)) else (buf,)


def _warn_deprecated(old: str, new: str) -> None:
    warnings.warn(
        f"ShardedAsyncServer.{old} is deprecated; use {new}, which accepts "
        f"a pytree delta directly (a stacked leading axis means a batch). "
        f"See README 'Engine API migration'.",
        DeprecationWarning, stacklevel=3)


def _finalize_root(params, opt_state, accs, w, norms, clips, staleness,
                   participation, spec, plan, server, rng, ops=None):
    """The root tail every tier flush shares: decode the combined modular
    sums into the noised mean PYTREE, apply the server optimizer, assemble
    the round metrics.

    ``accs``: tuple of per-chunk combined accumulators (the ParamPlan's
    layout — or, under an active compression spec, the WIRE layout: the
    sketch-domain sums, expanded here exactly once via ``ops``); ``w``:
    (B,) effective per-slot weights (staleness discount x present/valid
    gate); ``participation``: (B,) 1/0 present (streamed engines) or valid
    (batched engines) vector — the staleness_mean denominator.
    """
    metrics = agg.slot_metrics(w, norms, clips)
    mean = agg.finalize_plan_aggregate(accs, metrics["weight_total"], spec,
                                       plan, jax.random.fold_in(rng, 0xDEE),
                                       ops=ops)
    new_params, new_opt = server.apply(params, opt_state, mean)
    metrics["staleness_mean"] = agg.ordered_sum(staleness * participation) \
        / jnp.maximum(agg.ordered_sum(participation), 1.0)
    return new_params, new_opt, metrics


# ---------------------------------------------------------------------------
# One sharded global session (two_level=False) — the PR 4 tier
# ---------------------------------------------------------------------------
def build_sharded_masked_step(params, fl_cfg, *, num_leaves: int,
                              leaf_buffer: int, recover: bool = True,
                              masked: bool = True, mesh=None):
    """The sharded flush of the STREAMED engines (off / client / tee_stream)
    over ONE GLOBAL mask session.

    Returns jitted ``step(params, opt_state, mbuf, present, weights,
    staleness, norms, clips, session_key, rng)`` over the
    (num_leaves, leaf_buffer, D) int32 buffer of already-encoded (masked or
    plain) rows — the sharded analogue of
    ``async_fl.build_masked_async_buffer_step`` with
    ``buffer_size = num_leaves * leaf_buffer``, bit-identical to it.

    Leaf tier (shard_map): each leaf modular-sums its own present-gated
    slots and, under ``recover`` + ``masked``, sweeps ITS shard of the
    session graph's mixed edges (``secure_agg.recovery_sweep`` over a
    ``_partition_edges`` chunk) — cross-shard dropout recovery, since an
    edge's endpoints may live on different leaves while the sweep needs
    only the replicated (B,) present vector.  Root tier: one field-modulus
    ``psum`` (int32, mod 2^32) of the leaf partials, then decode /
    weight-normalize / central DP noise (drawn ONCE) / server optimizer.
    For the fault-isolated session-tree variant see
    ``build_two_level_masked_step``.
    """
    B = num_leaves * leaf_buffer
    spec = agg.make_spec(fl_cfg, B)
    if not spec.use_secure_agg:
        raise ValueError("the sharded tier aggregates in the secure-agg "
                         "integer field: set secure_agg_bits > 0")
    server = build_server_opt(fl_cfg)
    plan = agg.plan_for(params, fl_cfg)
    # wire-domain chunk widths: under an active compression spec the
    # buffers, masks and recovery sweeps all live at the COMPRESSED sizes
    # (the protocol primitives are width-agnostic); identity == the plan's
    wire = agg.plan_wire_chunks(spec, plan)
    if mesh is None:
        mesh = make_agg_mesh(num_leaves)

    def step(params, opt_state, mbuf, present, weights, staleness, norms,
             clips, session_key, rng):
        ops = agg.plan_operators(spec, plan, session_key)
        bufs = _as_chunks(mbuf)  # tuple of (L, Bl, padded_c)
        # global slot s = leaf * leaf_buffer + local
        rows = tuple(b.reshape(B, b.shape[-1]) for b in bufs)
        pres_full = present.reshape(B)

        if recover and masked:
            # per-chunk independent sessions: each chunk's graph is keyed
            # by its own session key, so each gets its own edge partition
            parts = tuple(
                _partition_edges(agg.make_mask_session(spec, k), num_leaves)
                for k in plan.session_keys(session_key))
            los = tuple(p[0] for p in parts)
            his = tuple(p[1] for p in parts)
            ews = tuple(p[2] for p in parts)

            def leaf_fn(rows_l, pres_l, pres_all, lo_l, hi_l, ew_l, skey):
                pres_i = pres_l.astype(jnp.int32)
                ckeys = plan.session_keys(skey)
                accs = []
                for c, wc in enumerate(wire):
                    acc = jnp.sum(rows_l[c] * pres_i[:, None],
                                  axis=0)  # int32, wraps mod 2^32
                    rec = sa.recovery_sweep((wc.size,), pres_all, lo_l[c],
                                            hi_l[c], ckeys[c], ew_l[c])
                    accs.append(acc + _pad_to(rec, wc.padded))
                # field-modulus combine, chunk-wise
                return jax.lax.psum(tuple(accs), LEAF_AXIS)

            accs = shard_map(
                leaf_fn, mesh=mesh,
                in_specs=(P(LEAF_AXIS), P(LEAF_AXIS), P(), P(LEAF_AXIS),
                          P(LEAF_AXIS), P(LEAF_AXIS), P()),
                out_specs=P(), check_vma=False,
            )(rows, pres_full, pres_full, los, his, ews, session_key)
        elif recover:  # streamed-unmasked partial flush: gate, no shares

            def leaf_fn(rows_l, pres_l):
                pres_i = pres_l.astype(jnp.int32)
                return jax.lax.psum(
                    tuple(jnp.sum(r * pres_i[:, None], axis=0)
                          for r in rows_l), LEAF_AXIS)

            accs = shard_map(
                leaf_fn, mesh=mesh, in_specs=(P(LEAF_AXIS), P(LEAF_AXIS)),
                out_specs=P(), check_vma=False)(rows, pres_full)
        else:  # complete session: masks provably cancel in the plain sum

            def leaf_fn(rows_l):
                return jax.lax.psum(
                    tuple(jnp.sum(r, axis=0) for r in rows_l), LEAF_AXIS)

            accs = shard_map(leaf_fn, mesh=mesh, in_specs=(P(LEAF_AXIS),),
                             out_specs=P(), check_vma=False)(rows)

        w = weights.reshape(B) * pres_full
        return _finalize_root(params, opt_state, accs, w, norms.reshape(B),
                              clips.reshape(B), staleness.reshape(B),
                              pres_full, spec, plan, server, rng, ops=ops)

    return jax.jit(step)


def build_sharded_buffer_step(params, fl_cfg, *, num_leaves: int,
                              leaf_buffer: int,
                              staleness_mode: str = "polynomial",
                              staleness_exponent: float = 0.5,
                              mask_mode: str = "off", mesh=None,
                              use_pallas: Optional[bool] = None):
    """The sharded BATCHED engine (raw f32 rows; "off" batched or "tee")
    over ONE GLOBAL mask session.

    The sharded analogue of ``async_fl.build_async_buffer_step``: returns
    jitted ``step(params, opt_state, buf, staleness, valid, rng)`` over a
    (num_leaves, leaf_buffer, D) f32 buffer.  Each leaf runs the full
    clip / weight / [device-noise] / stochastic-encode [/ in-enclave mask]
    row pipeline over its slot shard — ``aggregation.encode_and_sum_rows``
    with a :class:`secure_agg.MaskSession` view of the GLOBAL session at
    ``slot_offset = leaf * leaf_buffer``, i.e. the same fused Pallas
    ``weighted_quantize_accum``/PRF mask lanes as the single-host engine,
    pointed at the leaf's global slot range — and the root combines with a
    field-modulus psum + decode + one central noise draw + server opt.
    Session-wide stochastic draws are generated ONCE at the global (B, D)
    shape and sliced per leaf, so results are bit-identical to the
    single-host step at ``buffer_size = num_leaves * leaf_buffer``.
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    if mask_mode not in ("off", "tee"):
        raise ValueError(f"mask_mode {mask_mode!r}: expected 'off' or 'tee'")
    B = num_leaves * leaf_buffer
    spec = agg.make_spec(fl_cfg, B)
    if not spec.use_secure_agg:
        raise ValueError("the sharded tier aggregates in the secure-agg "
                         "integer field: set secure_agg_bits > 0")
    if not spec.compression.identity:
        raise ValueError(
            f"upload compression ({spec.compression.describe()}) runs on "
            "the STREAMING engines only — this batched step buffers raw "
            "f32 rows, so there is no compressed wire to save. Set "
            "compress_rate=1.0 here or switch to a streaming mode.")
    server = build_server_opt(fl_cfg)
    plan = agg.plan_for(params, fl_cfg)
    if mesh is None:
        mesh = make_agg_mesh(num_leaves)
    has_noise = spec.dev_noise > 0.0
    is_masked = mask_mode == "tee"
    Bl = leaf_buffer

    def step(params, opt_state, buf, staleness, valid, rng):
        bufs = _as_chunks(buf)  # tuple of (L, Bl, padded_c) f32
        rows = tuple(b.reshape(B, b.shape[-1]) for b in bufs)
        w_full = staleness_weight(staleness.reshape(B), staleness_mode,
                                  staleness_exponent) * valid.reshape(B)
        noise, uniforms = agg.plan_buffer_noise_and_uniforms(rng, B, spec,
                                                            plan)
        if noise is not None:
            noise = tuple(n * (spec.dev_noise * w_full)[:, None]
                          for n in noise)
        skey = jax.random.fold_in(rng, 0x7EE) if is_masked else None

        def leaf_fn(rows_l, w_l, u_l, *rest):
            rest = list(rest)
            n_l = rest.pop(0) if has_noise else None
            skey_l = rest.pop(0) if is_masked else None
            offset = jax.lax.axis_index(LEAF_AXIS) * Bl
            # every leaf derives the same GLOBAL per-chunk sessions from
            # the replicated key; only its slot-offset view differs
            sessions = (agg.plan_sessions(spec, plan, skey_l,
                                          slot_offset=offset)
                        if is_masked else None)
            accs, nrm, clipped = agg.encode_plan_rows(
                rows_l, w_l, u_l, n_l, spec, plan, sessions=sessions,
                use_pallas=use_pallas)
            return jax.lax.psum(accs, LEAF_AXIS), nrm, clipped

        args = [rows, w_full, uniforms]
        in_specs = [P(LEAF_AXIS), P(LEAF_AXIS), P(LEAF_AXIS)]
        if has_noise:
            args.append(noise)
            in_specs.append(P(LEAF_AXIS))
        if is_masked:
            args.append(skey)
            in_specs.append(P())
        accs, nrm, was_clipped = shard_map(
            leaf_fn, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(P(), P(LEAF_AXIS), P(LEAF_AXIS)), check_vma=False,
        )(*args)

        return _finalize_root(params, opt_state, accs, w_full, nrm,
                              was_clipped, staleness.reshape(B),
                              valid.reshape(B), spec, plan, server, rng)

    return jax.jit(step)


# ---------------------------------------------------------------------------
# The session tree (two_level=True): leaf sessions -> root session
# ---------------------------------------------------------------------------
def build_two_level_masked_step(params, fl_cfg, *, num_leaves: int,
                                leaf_buffer: int, recover: bool = True,
                                masked: bool = True, mesh=None):
    """The session-tree flush of the STREAMED engines (off/client/tee_stream).

    Same signature and buffer layout as ``build_sharded_masked_step``, but
    the (num_leaves, leaf_buffer, D) buffer holds rows masked under
    PER-LEAF local sessions (``leaf_session``), and the flush is a true
    two-level aggregation:

      leaf tier (shard_map; several logical leaves per device when
      num_leaves > mesh size):  gated modular partial sum over the leaf's
      own present slots  +  the leaf's OWN recovery sweep (its local
      session's edges, gated by its local (Bl,) present vector — one
      leaf's dropout recovery never touches another leaf's edges)  +  the
      leaf's ROOT-session mask when the leaf is alive (the root only ever
      combines masked partials);

      root tier:  field-modulus psum  +  root recovery for DEAD leaves
      (one ``num_leaves``-slot sweep)  →  decode / weight-normalize /
      central DP noise (once) / server optimizer.

    Bit-identical to the single-host engines at
    ``buffer_size = num_leaves * leaf_buffer`` (the encoded q-streams are
    identical; each level's masks cancel or are recovered exactly), and
    the partial-flush decode equals the flat survivor aggregate under
    client dropout, whole-leaf dropout, and both combined — test-enforced.
    """
    B = num_leaves * leaf_buffer
    spec = agg.make_spec(fl_cfg, B)
    if not spec.use_secure_agg:
        raise ValueError("the sharded tier aggregates in the secure-agg "
                         "integer field: set secure_agg_bits > 0")
    server = build_server_opt(fl_cfg)
    plan = agg.plan_for(params, fl_cfg)
    # the session tree runs at the WIRE widths too: every leaf session,
    # root mask and recovery sweep operates on the compressed rows
    wire = agg.plan_wire_chunks(spec, plan)
    if mesh is None:
        mesh = make_leaf_mesh(num_leaves)
    lpd = leaves_per_device(num_leaves, mesh)
    L, Bl = num_leaves, leaf_buffer

    def step(params, opt_state, mbuf, present, weights, staleness, norms,
             clips, session_key, rng):
        ops = agg.plan_operators(spec, plan, session_key)
        bufs = _as_chunks(mbuf)  # tuple of (L, Bl, padded_c)

        def dev_fn(rows_b, pres_b, skey):
            # rows_b: per-chunk (lpd, Bl, padded_c); pres_b: (lpd, Bl) —
            # THIS device's leaves
            dev = jax.lax.axis_index(LEAF_AXIS)
            gleaves = dev * lpd + jnp.arange(lpd, dtype=jnp.int32)
            ckeys = plan.session_keys(skey)
            # the root sessions are leaf-independent: derive them once per
            # device, not once per vmapped logical leaf
            rsess = (tuple(root_session(spec, k, L) for k in ckeys)
                     if recover and masked else None)

            def one_leaf(g, rows_l, pres_l):
                if not recover:  # complete session: local masks cancel
                    return tuple(jnp.sum(r, axis=0) for r in rows_l)
                pres_i = pres_l.astype(jnp.int32)
                alive = (pres_i.sum() > 0).astype(jnp.int32)
                accs = []
                for c, wc in enumerate(wire):
                    acc = jnp.sum(rows_l[c] * pres_i[:, None],
                                  axis=0)  # mod 2^32
                    if masked:
                        # fault isolation: ONLY this leaf's session edges,
                        # gated by ONLY this leaf's present vector — per
                        # chunk, under the chunk's own session tree
                        lsess = leaf_session(spec, ckeys[c], g, Bl)
                        acc = acc + _pad_to(
                            lsess.recovery((wc.size,), pres_l), wc.padded)
                        acc = acc + _pad_to(
                            alive * rsess[c].mask((wc.size,), g), wc.padded)
                    accs.append(acc)
                return tuple(accs)

            accs = jax.vmap(one_leaf)(gleaves, rows_b, pres_b)
            return jax.lax.psum(
                jax.tree.map(lambda a: jnp.sum(a, axis=0, dtype=a.dtype),
                             accs), LEAF_AXIS)

        accs = shard_map(
            dev_fn, mesh=mesh,
            in_specs=(P(LEAF_AXIS), P(LEAF_AXIS), P()),
            out_specs=P(), check_vma=False,
        )(bufs, present, session_key)

        pres_full = present.reshape(B)
        if recover and masked:
            # root tier: a dead leaf is one absent slot of each chunk's
            # L-slot root session — recover its shares with root sweeps
            alive = (present.reshape(L, Bl).sum(axis=1) > 0)
            alive_f = alive.astype(jnp.float32)
            ckeys = plan.session_keys(session_key)
            accs = tuple(
                acc + _pad_to(root_session(spec, ckeys[c], L).recovery(
                    (wc.size,), alive_f), wc.padded)
                for c, (acc, wc) in enumerate(zip(accs, wire)))

        w = weights.reshape(B) * pres_full
        return _finalize_root(params, opt_state, accs, w, norms.reshape(B),
                              clips.reshape(B), staleness.reshape(B),
                              pres_full, spec, plan, server, rng, ops=ops)

    return jax.jit(step)


def build_two_level_buffer_step(params, fl_cfg, *, num_leaves: int,
                                leaf_buffer: int,
                                staleness_mode: str = "polynomial",
                                staleness_exponent: float = 0.5,
                                mesh=None,
                                use_pallas: Optional[bool] = None):
    """The session-tree BATCHED "tee" engine: raw f32 rows, per-leaf
    in-enclave mask lanes.

    Each leaf runs ``aggregation.encode_and_sum_rows`` under its OWN local
    :class:`secure_agg.MaskSession` (``num_slots = leaf_buffer``,
    ``slot_offset = 0`` — the whole-session fast path, per leaf), so the
    fused Pallas/PRF lane generates only O(Bl * k) streams per leaf and
    every leaf's masks cancel inside its own accumulator.  Session-wide
    noise/uniform draws are generated once at the (B, D) shape and sliced
    per leaf; the root combines with a field-modulus psum.  Bit-identical
    to the single-host batched "tee" step (identical q-streams; each leaf
    session's masks cancel exactly as the global session's did).
    """
    if use_pallas is None:
        use_pallas = on_tpu()
    B = num_leaves * leaf_buffer
    spec = agg.make_spec(fl_cfg, B)
    if not spec.use_secure_agg:
        raise ValueError("the sharded tier aggregates in the secure-agg "
                         "integer field: set secure_agg_bits > 0")
    if not spec.compression.identity:
        raise ValueError(
            f"upload compression ({spec.compression.describe()}) runs on "
            "the STREAMING engines only — this batched step buffers raw "
            "f32 rows, so there is no compressed wire to save. Set "
            "compress_rate=1.0 here or switch to a streaming mode.")
    server = build_server_opt(fl_cfg)
    plan = agg.plan_for(params, fl_cfg)
    if mesh is None:
        mesh = make_leaf_mesh(num_leaves)
    lpd = leaves_per_device(num_leaves, mesh)
    has_noise = spec.dev_noise > 0.0
    L, Bl = num_leaves, leaf_buffer

    def step(params, opt_state, buf, staleness, valid, rng):
        bufs = _as_chunks(buf)  # tuple of (L, Bl, padded_c) f32
        w_full = staleness_weight(staleness.reshape(B), staleness_mode,
                                  staleness_exponent) * valid.reshape(B)
        noise, uniforms = agg.plan_buffer_noise_and_uniforms(rng, B, spec,
                                                            plan)
        if noise is not None:
            noise = tuple(n * (spec.dev_noise * w_full)[:, None]
                          for n in noise)
        skey = jax.random.fold_in(rng, 0x7EE)
        w3 = w_full.reshape(L, Bl)
        u3 = tuple(u.reshape(L, Bl, u.shape[-1]) for u in uniforms)
        n3 = (None if noise is None
              else tuple(n.reshape(L, Bl, n.shape[-1]) for n in noise))

        def dev_fn(rows_b, w_b, u_b, *rest):
            rest = list(rest)
            n_b = rest.pop(0) if has_noise else None
            skey_b = rest.pop(0)
            dev = jax.lax.axis_index(LEAF_AXIS)
            gleaves = dev * lpd + jnp.arange(lpd, dtype=jnp.int32)
            ckeys = plan.session_keys(skey_b)

            def one_leaf(g, rows_l, w_l, u_l, n_l):
                sessions = tuple(leaf_session(spec, k, g, Bl)
                                 for k in ckeys)
                return agg.encode_plan_rows(
                    rows_l, w_l, u_l, n_l, spec, plan, sessions=sessions,
                    use_pallas=use_pallas)

            # n_b is None when device noise is off — an empty pytree, which
            # vmap maps over trivially
            accs, nrm, clipped = jax.vmap(one_leaf)(gleaves, rows_b, w_b,
                                                    u_b, n_b)
            return (jax.lax.psum(
                jax.tree.map(lambda a: jnp.sum(a, axis=0, dtype=a.dtype),
                             accs), LEAF_AXIS), nrm, clipped)

        args = [bufs, w3, u3]
        in_specs = [P(LEAF_AXIS), P(LEAF_AXIS), P(LEAF_AXIS)]
        if has_noise:
            args.append(n3)
            in_specs.append(P(LEAF_AXIS))
        args.append(skey)
        in_specs.append(P())
        accs, nrm, was_clipped = shard_map(
            dev_fn, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(P(), P(LEAF_AXIS), P(LEAF_AXIS)), check_vma=False,
        )(*args)
        nrm, was_clipped = nrm.reshape(B), was_clipped.reshape(B)

        return _finalize_root(params, opt_state, accs, w_full, nrm,
                              was_clipped, staleness.reshape(B),
                              valid.reshape(B), spec, plan, server, rng)

    return jax.jit(step)


class ShardedAsyncServer:
    """Buffered asynchronous aggregation over the leaf/root tier.

    The "Meta scale" facade over a device-resident
    (num_leaves, leaf_buffer, D) buffer physically sharded over the leaf
    mesh axis (``launch.sharding.hierarchy_shardings``) — no single host
    ever materializes the whole round.  ``num_leaves``/``leaf_buffer``/
    ``two_level`` default from ``FLConfig`` (``fl_cfg.num_leaves`` etc.);
    ``num_leaves`` may exceed the visible device count — logical leaves
    are multiplexed onto the mesh (``launch.mesh.make_leaf_mesh``).

    Session topology (``two_level``):
      False — ONE pairwise-mask session spans all
              ``num_leaves * leaf_buffer`` global slots (slot ``s`` lives
              on leaf ``s // leaf_buffer``); recovery edges cross leaves.
      True  — a SESSION TREE: each leaf masks its rows under its own
              ``leaf_buffer``-slot local session and flushes a masked
              partial into a ``num_leaves``-slot root session
              (fault-isolated recovery; see the module docstring).

    Arrival ingestion is BATCHED and DESTINATION-SHARDED: ``push_batch``
    takes a (K,)-stacked batch of raw deltas, routes each row to its
    destination leaf (a host-side index shuffle — no row math), and runs
    the clip/weight/encode[+mask] pipeline INSIDE a shard_map, each leaf
    encoding exactly the rows addressed to it — no central (K, D) encode
    precedes the scatter, so ingest bandwidth scales with the leaf count.
    Rows are bit-identical to K sequential ``AsyncServer`` pushes (same
    per-slot PRF streams); ``push_encoded_batch`` lands client-encoded
    ``ClientPush`` rows with one jitted scatter (the server never encodes
    in mask_mode="client").

    mask_mode semantics match ``AsyncServer`` ("off" always streams here —
    the tier requires the integer field anyway); the flush builders are
    selected by (mask mode, two_level), all bit-identical to the
    single-host engines at ``buffer_size = num_leaves * leaf_buffer``.
    """

    def __init__(self, params, fl_cfg, *, num_leaves: Optional[int] = None,
                 leaf_buffer: Optional[int] = None,
                 staleness_exponent: float = 0.5,
                 staleness_mode: str = "polynomial",
                 mask_mode: str = "off", session_seed: int = 0x5A5E,
                 two_level: Optional[bool] = None,
                 mesh=None, use_pallas: Optional[bool] = None,
                 strict: bool = True,
                 telemetry: Optional["tele.Telemetry"] = None):
        if mask_mode not in ("off", "tee", "tee_stream", "client"):
            raise ValueError(f"mask_mode {mask_mode!r}")
        num_leaves = num_leaves or fl_cfg.num_leaves
        leaf_buffer = leaf_buffer or fl_cfg.leaf_buffer
        if not num_leaves or not leaf_buffer:
            raise ValueError(
                "the tier's shape is unset: pass num_leaves/leaf_buffer "
                "or set FLConfig.num_leaves/leaf_buffer")
        if two_level is None:
            two_level = fl_cfg.two_level
        self.params = params
        self.fl_cfg = fl_cfg
        self.num_leaves = num_leaves
        self.leaf_buffer = leaf_buffer
        self.buffer_size = B = num_leaves * leaf_buffer
        self.staleness_exponent = staleness_exponent
        self.staleness_mode = staleness_mode
        self.mask_mode = mask_mode
        self.two_level = two_level
        self.version = 0
        self.last_metrics: Optional[dict] = None
        self._applied_updates = 0
        self._fill = 0
        # fault tolerance (mirrors AsyncServer): strict=True raises on
        # protocol violations, strict=False counts-and-drops; duplicate
        # deliveries of a tokened push are idempotent no-ops either way.
        # A leaf marked dead (mark_leaf_dead) drops out of slot allocation
        # and quorum accounting for the REST OF ITS SESSION; its buffered
        # rows are recovered exactly like client dropouts (present-gated).
        self.strict = strict
        self.flush_quorum = float(getattr(fl_cfg, "flush_quorum", 0.0))
        # one registry for every counter/span the tier emits (eid = an
        # EPHEMERAL random id separating this instance's series)
        self.telemetry = (telemetry if telemetry is not None
                          else tele.get_default())
        self._eid = tele.new_session_id()
        self._tl = {"engine": "tier", "eid": self._eid}
        # deprecated PR 8 spelling: a dict view over the registry counters
        self.fault_metrics = tele.TelemetryCounterView(
            self.telemetry, FAULT_METRIC_KEYS + ("dead_leaves",), **self._tl)
        self._token_counter = 0
        self._delivered_tokens: set = set()
        self._dead_leaves: set = set()
        self._session_base = jax.random.PRNGKey(session_seed)
        self._push_base = jax.random.PRNGKey(0xA5)
        if use_pallas is None:
            use_pallas = on_tpu()
        if mesh is None:
            mesh = (make_leaf_mesh(num_leaves) if two_level
                    else make_agg_mesh(num_leaves))
        self.mesh = mesh
        lpd = leaves_per_device(num_leaves, mesh)
        shardings = hierarchy_shardings(self.mesh)
        s_buf, s_slot = shardings["buffer"], shardings["per_slot"]

        spec = agg.make_spec(fl_cfg, B)
        if not spec.use_secure_agg:
            raise ValueError("the sharded tier aggregates in the secure-agg "
                             "integer field: set secure_agg_bits > 0")
        self._spec = spec
        plan = agg.plan_for(params, fl_cfg)
        self._plan = plan
        # wire-domain widths (== the plan's under the identity spec)
        wire = agg.plan_wire_chunks(spec, plan)
        self._opt_state = build_server_opt(fl_cfg).init(params)
        L, Bl = num_leaves, leaf_buffer
        # enclave quantized wire: tee modes can ship packed sub-32-bit
        # words instead of the raw f32 delta (FLConfig.enclave_wire_bits)
        ebits = int(getattr(fl_cfg, "enclave_wire_bits", 0))
        self._enclave_bits = ebits if mask_mode in ("tee", "tee_stream") \
            else 0
        if self._enclave_bits:
            emod = (1 << ebits) if ebits < 32 else (1 << 32)
            evr = float(fl_cfg.secure_agg_range)

            @jax.jit
            def _enclave_wire(deltas, rng):
                """CLIENT-side jit over a (K,)-stacked batch: stochastic
                quantize -> packed uint32 words (the actual wire) ->
                enclave-side unpack -> dequantize.  No f32 delta crosses
                the wire; the tier ingests the quantized reconstruction."""
                K = jax.tree.leaves(deltas)[0].shape[0]

                def one(delta, k):
                    xs = plan.chunk_arrays(delta)
                    ks = jax.random.split(k, len(xs))
                    outs, words = [], []
                    for x, kk in zip(xs, ks):
                        q = sa.quantize(x, ebits, evr, kk)
                        w = sa.pack_residues(sa.to_field(q, emod), emod)
                        q2 = sa.recenter(
                            sa.unpack_residues(w, x.shape[-1], emod), emod)
                        outs.append(sa.dequantize(q2, ebits, evr))
                        words.append(w)
                    return plan.unchunk(tuple(outs)), tuple(words)

                return jax.vmap(one)(deltas, jax.random.split(rng, K))

            self._enclave_wire = _enclave_wire
            self._enclave_seq = 0
            self._enclave_base = jax.random.PRNGKey(0xE7C)
        zslot = lambda: jax.device_put(jnp.zeros((L, Bl), jnp.float32),
                                       s_slot)
        self._stal = zslot()
        # per-GLOBAL-slot presence (host metadata): sessions fill out of
        # order — concurrent clients push for assigned slots on any leaf
        self._present = [False] * B
        self._streaming = mask_mode != "tee"
        s_mode, s_exp = staleness_mode, staleness_exponent
        masked = mask_mode not in ("off", "tee")

        def row_sessions(skey, gslot):
            """The (per-chunk sessions, mask-slot) a row at GLOBAL slot
            ``gslot`` is masked under — the single construction point both
            the destination-sharded server ingest and the client-side
            ``encode_push`` share, so their rows are bit-equal."""
            ckeys = plan.session_keys(skey)
            if two_level:
                leaf, mslot = gslot // Bl, gslot % Bl
                return (tuple(leaf_session(spec, k, leaf, Bl)
                              for k in ckeys), mslot)
            return tuple(agg.make_mask_session(spec, k)
                         for k in ckeys), gslot

        def encode_row(chunks_d, gslot, stal, skey, pkey):
            """One arrival's jitted encode pipeline, traceable in the slot.

            ``chunks_d`` is the plan's tuple of PADDED per-chunk flat rows.
            PRF streams are keyed by the GLOBAL slot
            (``fold_in(push_key, gslot)``) in both topologies, so encoded
            q-streams — and therefore decoded aggregates — are
            bit-identical to sequential single-host pushes.
            """
            rng = jax.random.fold_in(pkey, gslot)
            w = staleness_weight(stal, s_mode, s_exp)
            xs = tuple(x[..., :ck.size]
                       for x, ck in zip(chunks_d, plan.chunks))
            if masked:
                sessions, mslot = row_sessions(skey, gslot)
            else:
                sessions, mslot = None, 0
            # compression operators are keyed by the ENGINE session key
            # (not the leaf keys): every contributor of the round shares
            # one operator per chunk, so sums commute with it
            ops = agg.plan_operators(spec, plan, skey)
            rows, nrm, clipped = agg.encode_plan_flat(
                xs, w, mslot, spec, plan, sessions, rng, masked=masked,
                use_pallas=use_pallas, ops=ops)
            return rows, w, nrm, clipped

        if self._streaming:
            self._bufs = tuple(
                jax.device_put(jnp.zeros((L, Bl, wc.padded), jnp.int32),
                               s_buf) for wc in wire)
            self._wts, self._norms, self._clips = zslot(), zslot(), zslot()
            build_masked = (build_two_level_masked_step if two_level
                            else build_sharded_masked_step)
            self._step = build_masked(
                params, fl_cfg, num_leaves=L, leaf_buffer=Bl, recover=False,
                masked=masked, mesh=self.mesh)
            self._flush_step = None
            self._build_flush_step = lambda: build_masked(
                self.params, fl_cfg, num_leaves=L, leaf_buffer=Bl,
                recover=True, masked=masked, mesh=self.mesh)

            @jax.jit
            def _ingest_sharded(buf, wts, norms, clips, stal, deltas, idx,
                                lslot, valid, stals, session_key, push_key):
                """Destination-sharded ingest of one routed arrival batch.

                ``idx``/``lslot``/``valid``/``stals``: (L, kb) per-leaf
                routing tables (kb = most arrivals any leaf received this
                batch; padding rows carry valid=0).  The raw rows are
                chunked per the plan and gathered to their destination
                leaves (a memory move — per-chunk, never a concatenated
                (K, D) block), and ALL row math —
                clip/weight/stochastic-encode[+mask] — runs inside the
                shard_map, each leaf encoding only its own arrivals.
                Padded rows are encoded-and-dropped (their writes target
                local slot Bl, out of range -> scatter-drop).
                """
                chunks_raw = plan.chunk_arrays(deltas, leading=1, pad=True)
                kb = idx.shape[1]
                routed = tuple(_take_rows(cr, idx.reshape(-1))
                               .reshape(L, kb, -1) for cr in chunks_raw)

                def dev_fn(buf_b, wts_b, norms_b, clips_b, stal_b, routed_b,
                           lslot_b, valid_b, stals_b, skey, pkey):
                    dev = jax.lax.axis_index(LEAF_AXIS)
                    gleaves = dev * lpd + jnp.arange(lpd, dtype=jnp.int32)
                    # ONE vmap over this device's (leaf, row) pairs: the
                    # rows' encode is one kernel launch whose grid walks
                    # the pairs
                    kb = lslot_b.shape[1]
                    gslots = (gleaves[:, None] * Bl + lslot_b).reshape(-1)

                    def flat(a):
                        return a.reshape((lpd * kb,) + a.shape[2:])

                    def per_leaf(a):
                        return a.reshape((lpd, kb) + a.shape[1:])

                    rows_e, w, nrm, cl = jax.vmap(
                        lambda r, s, t: encode_row(r, s, t, skey, pkey))(
                        tuple(flat(r) for r in routed_b), gslots,
                        flat(stals_b))

                    def one_leaf(buf_l, wts_l, norms_l, clips_l, stal_l,
                                 rows_l, w_l, nrm_l, cl_l, sl, vld, st):
                        tgt = jnp.where(vld > 0, sl, Bl)  # Bl -> dropped
                        return (tuple(b.at[tgt].set(r, mode="drop")
                                      for b, r in zip(buf_l, rows_l)),
                                wts_l.at[tgt].set(w_l, mode="drop"),
                                norms_l.at[tgt].set(nrm_l, mode="drop"),
                                clips_l.at[tgt].set(cl_l, mode="drop"),
                                stal_l.at[tgt].set(st, mode="drop"))

                    return jax.vmap(one_leaf)(
                        buf_b, wts_b, norms_b, clips_b, stal_b,
                        tuple(per_leaf(r) for r in rows_e), per_leaf(w),
                        per_leaf(nrm), per_leaf(cl), lslot_b, valid_b,
                        stals_b)

                return shard_map(
                    dev_fn, mesh=self.mesh,
                    in_specs=(P(LEAF_AXIS),) * 9 + (P(), P()),
                    out_specs=(P(LEAF_AXIS),) * 5, check_vma=False,
                )(buf, wts, norms, clips, stal, routed, lslot, valid, stals,
                  session_key, push_key)

            self._ingest_sharded = _ingest_sharded

            @jax.jit
            def _encode_batch(deltas, slots, stals, session_key, push_key):
                """The CLIENT-side vmapped encode (mask_mode='client'):
                produces the rows ``encode_push`` hands back to the
                caller, in WIRE FORMAT.  Runs the exact ``encode_row``
                pipeline of the sharded server ingest (so client-encoded
                and server-encoded rows are bit-identical), then each
                chunk's session ``reduce``s its rows — canonical field
                residues bit-packed into the dense uint32 stream.  Every
                session of the tree shares the ENGINE field, so one
                session per chunk decides the width for the whole batch."""

                def one(delta, slot, s):
                    chunks_d = plan.chunk_arrays(delta, pad=True)
                    return encode_row(chunks_d, slot, s, session_key,
                                      push_key)

                rows, w, nrm, clipped = jax.vmap(one)(deltas, slots, stals)
                wire_sessions, _ = row_sessions(session_key, 0)
                rows = tuple(sess.reduce(r)
                             for sess, r in zip(wire_sessions, rows))
                return rows, w, nrm, clipped

            @jax.jit
            def _scatter_packed(bufs, wts, norms, clips, stal, wrows, idx,
                                lslot, valid, stals, w, nrm, clipped):
                """Destination-sharded landing of client-packed wire rows.

                The PACKED uint32 word streams are what travels: they are
                routed to their destination leaves by the same host-built
                (L, kb) tables as the raw ingest — a memory move of the
                narrow wire payload, never the widened rows — and expanded
                back to int32 field residues INSIDE the shard_map, each
                leaf unpacking only its own arrivals.  Padding rows unpack
                to garbage nobody reads (their writes target local slot
                Bl, out of range -> scatter-drop)."""
                kb = idx.shape[1]
                flat = idx.reshape(-1)
                routed = tuple(_take_rows(wr, flat).reshape(L, kb, -1)
                               for wr in wrows)
                wv = jnp.take(w, flat).reshape(L, kb)
                nv = jnp.take(nrm, flat).reshape(L, kb)
                cv = jnp.take(clipped, flat).reshape(L, kb)

                def dev_fn(buf_b, wts_b, norms_b, clips_b, stal_b,
                           routed_b, lslot_b, valid_b, stals_b, w_b, n_b,
                           c_b):
                    def one_leaf(buf_l, wts_l, norms_l, clips_l, stal_l,
                                 wr_l, sl, vld, st, wl, nl, cl):
                        rows = tuple(
                            sa.unpack_residues(r, wc.padded,
                                               spec.field_modulus)
                            for r, wc in zip(wr_l, wire))
                        tgt = jnp.where(vld > 0, sl, Bl)  # Bl -> dropped
                        return (tuple(b.at[tgt].set(r, mode="drop")
                                      for b, r in zip(buf_l, rows)),
                                wts_l.at[tgt].set(wl, mode="drop"),
                                norms_l.at[tgt].set(nl, mode="drop"),
                                clips_l.at[tgt].set(cl, mode="drop"),
                                stal_l.at[tgt].set(st, mode="drop"))

                    return jax.vmap(one_leaf)(
                        buf_b, wts_b, norms_b, clips_b, stal_b, routed_b,
                        lslot_b, valid_b, stals_b, w_b, n_b, c_b)

                return shard_map(
                    dev_fn, mesh=self.mesh,
                    in_specs=(P(LEAF_AXIS),) * 12,
                    out_specs=(P(LEAF_AXIS),) * 5, check_vma=False,
                )(bufs, wts, norms, clips, stal, routed, lslot, valid,
                  stals, wv, nv, cv)

            self._encode_batch = _encode_batch
            self._scatter_packed = _scatter_packed
        else:  # "tee": raw rows, the batched in-enclave mask lane at flush
            self._bufs = tuple(
                jax.device_put(jnp.zeros((L, Bl, ck.padded), jnp.float32),
                               s_buf) for ck in plan.chunks)
            self._valid = zslot()
            if two_level:
                self._step = build_two_level_buffer_step(
                    params, fl_cfg, num_leaves=L, leaf_buffer=Bl,
                    staleness_mode=staleness_mode,
                    staleness_exponent=staleness_exponent, mesh=self.mesh,
                    use_pallas=use_pallas)
            else:
                self._step = build_sharded_buffer_step(
                    params, fl_cfg, num_leaves=L, leaf_buffer=Bl,
                    staleness_mode=staleness_mode,
                    staleness_exponent=staleness_exponent, mask_mode="tee",
                    mesh=self.mesh, use_pallas=use_pallas)

            @jax.jit
            def _scatter_raw(bufs, stal, valid, leaf, local, deltas, s):
                rows = plan.chunk_arrays(deltas, leading=1, pad=True)
                return (tuple(b.at[leaf, local].set(r)
                              for b, r in zip(bufs, rows)),
                        stal.at[leaf, local].set(s),
                        valid.at[leaf, local].set(jnp.ones_like(s)))

            self._scatter_raw = _scatter_raw

    # -- plan / buffer views ------------------------------------------------
    @property
    def plan(self) -> agg.ParamPlan:
        """The :class:`aggregation.ParamPlan` the tier's buffers, sessions
        and encode pipeline are laid out by."""
        return self._plan

    @property
    def _buf(self):
        """Legacy view of the chunked buffer: the bare array of a
        single-chunk plan (the flat (L, Bl, D) layout older callers poke),
        else the per-chunk tuple."""
        return self._bufs[0] if len(self._bufs) == 1 else self._bufs

    # -- session bookkeeping ------------------------------------------------
    def _session_key(self):
        """PRNG key of the current mask session (tree) (= buffer round)."""
        return jax.random.fold_in(self._session_base, self.version)

    def _new_token(self) -> int:
        self._token_counter += 1
        return self._token_counter

    def _span(self, name: str, **labels):
        """Tier span: labeled with the ephemeral eid and the session."""
        return self.telemetry.span(
            name, round=self.version,
            topology="tree" if self.two_level else "flat",
            **self._tl, **labels)

    @property
    def live_capacity(self) -> int:
        """Session slots on leaves still alive — the quorum denominator."""
        return self.buffer_size - len(self._dead_leaves) * self.leaf_buffer

    def open_slots(self) -> List[int]:
        """Unfilled session positions on LIVE leaves."""
        Bl = self.leaf_buffer
        return [s for s, p in enumerate(self._present)
                if not p and (s // Bl) not in self._dead_leaves]

    def mark_leaf_dead(self, leaf: int) -> List[int]:
        """Declare one leaf aggregator dead for the rest of this session.

        Its buffered contributions are LOST (present flags cleared, so the
        flush recovers their mask shares exactly like client dropouts — in
        the session tree via one root-slot sweep); its slots leave the
        allocator (``open_slots``/``_take_slots``) and the quorum
        denominator.  The fault-injection layer re-routes the leaf's queued
        (undelivered) arrivals to surviving leaves.  Returns the global
        slots whose contributions were lost.  Leaves revive at the next
        session roll (the restarted process joins the next session).
        """
        if not 0 <= leaf < self.num_leaves:
            raise ValueError(f"leaf {leaf} outside the {self.num_leaves}-leaf "
                             "tier")
        if leaf in self._dead_leaves:
            return []
        self._dead_leaves.add(leaf)
        self.fault_metrics["dead_leaves"] += 1
        Bl = self.leaf_buffer
        lost = [s for s in range(leaf * Bl, (leaf + 1) * Bl)
                if self._present[s]]
        for s in lost:
            self._present[s] = False
        self._fill -= len(lost)
        self.fault_metrics["lost_contributions"] += len(lost)
        self.telemetry.gauge("buffered_contributions", self._fill,
                             **self._tl)
        if not self._streaming:
            # the "tee" engine gates rows by the device-side valid plane
            self._valid = self._valid.at[leaf].set(
                jnp.zeros((Bl,), jnp.float32))
        return lost

    def _take_slots(self, k: int) -> List[int]:
        free = self.open_slots()
        if len(free) < k:
            raise ValueError(
                f"batch of {k} exceeds the session's {len(free)} open slots "
                f"(route arrival batches per session)")
        return free[:k]

    def _slot_open(self, s: int) -> bool:
        return (0 <= s < self.buffer_size and not self._present[s]
                and (s // self.leaf_buffer) not in self._dead_leaves)

    def _check_slots(self, slots) -> None:
        """Every batch slot must be a distinct OPEN session position —
        a repeat would overwrite a row while ``_fill`` still counts it,
        silently corrupting the session's modular sum.  Slots on dead
        leaves are closed (their leaf cannot ingest)."""
        if len(set(slots)) != len(slots):
            raise ValueError(f"duplicate slots in batch: {list(slots)}")
        for s in slots:
            if not self._slot_open(s):
                raise ValueError(
                    f"slot {s} is not an open position of session "
                    f"{self.version}")

    def _leaf_local(self, slots: Sequence[int]):
        s = jnp.asarray(slots, jnp.int32)
        return s // self.leaf_buffer, s % self.leaf_buffer

    def _staleness_of(self, client_version, k: int) -> np.ndarray:
        """(k,) staleness values for a scalar or (k,) ``client_version``."""
        if jnp.ndim(client_version) == 0:
            return np.full((k,), float(self.version - client_version),
                           np.float32)
        return self.version - np.asarray(client_version, np.float32)

    def _route_by_leaf(self, slots: Sequence[int], stals: np.ndarray):
        """Group one arrival batch by DESTINATION leaf.

        Returns (idx, lslot, valid, stals) each (num_leaves, kb) — the
        routing tables the destination-sharded ingest consumes.  Pure
        index bookkeeping on host ints; no row payload is touched.

        ``kb`` is the most arrivals any single leaf received, rounded up
        to a power of two (bounds the distinct ingest shapes jit ever
        sees to log2(leaf_buffer) variants).  Every leaf encodes kb rows
        — padding rows are encoded-and-dropped — so a batch skewed onto
        one leaf costs that leaf's kb on every device: the
        bandwidth-scales-with-leaves property holds for leaf-BALANCED
        arrival batches, which is what a front-end router feeding the
        tier produces (and what the default contiguous slot allocation
        approximates one leaf at a time).
        """
        L, Bl = self.num_leaves, self.leaf_buffer
        per: List[List[int]] = [[] for _ in range(L)]
        for pos, s in enumerate(slots):
            per[s // Bl].append(pos)
        kb = max(1, max(len(p) for p in per))
        kb = min(Bl, 1 << (kb - 1).bit_length())  # pow2: bounded retraces
        idx = np.zeros((L, kb), np.int32)
        lsl = np.zeros((L, kb), np.int32)
        valid = np.zeros((L, kb), np.float32)
        st = np.zeros((L, kb), np.float32)
        for leaf, positions in enumerate(per):
            for j, pos in enumerate(positions):
                idx[leaf, j] = pos
                lsl[leaf, j] = slots[pos] % Bl
                valid[leaf, j] = 1.0
                st[leaf, j] = stals[pos]
        return (jnp.asarray(idx), jnp.asarray(lsl), jnp.asarray(valid),
                jnp.asarray(st))

    # -- client protocol ----------------------------------------------------
    def pull(self) -> Tuple[Any, int]:
        return self.params, self.version

    def push(self, delta, client_version, rng=None,
             slots: Optional[Sequence[int]] = None,
             push_ids: Optional[Sequence[int]] = None) -> None:
        """Push one raw delta pytree — or a batch of them.

        The ONE ingest entry point, shared in shape with
        ``AsyncServer.push``: ``delta`` is either a single model-shaped
        pytree or a (K,)-STACKED pytree (every leaf grows one leading
        axis), in which case the batch is routed to its destination leaves
        on host (index bookkeeping only) and encoded INSIDE a shard_map —
        each leaf runs the jitted clip/weight/encode[+mask] pipeline over
        exactly the rows addressed to it — then written in place; rows are
        bit-identical to K sequential pushes.  ``client_version`` may be a
        scalar or a (K,) sequence (mixed staleness within one arrival
        batch).  ``push_ids`` (one idempotence token per row) makes
        retried/duplicated raw rows counted no-ops, mirroring
        ``ClientPush.token`` on the encoded path.
        """
        k = batch_count(delta, self.params)
        if k is None:
            delta = jax.tree.map(lambda x: x[None], delta)
            if slots is not None and not isinstance(slots, (list, tuple)):
                slots = [slots]
            if push_ids is not None and not isinstance(push_ids,
                                                       (list, tuple)):
                push_ids = [push_ids]
        self._push_impl(delta, client_version, rng=rng, slots=slots,
                        push_ids=push_ids)

    def encode_push(self, delta, client_version, rng=None,
                    slot=None):
        """The CLIENT half of mask_mode='client' (see
        ``AsyncServer.encode_push``) against a GLOBAL session slot.

        Accepts a single delta pytree (returns one :class:`ClientPush`) or
        a (K,)-stacked batch (returns a list).  ``rng`` is accepted for
        signature parity with ``AsyncServer.encode_push`` and unused: the
        tier's per-slot PRF streams are fixed by the session so that rows
        are bit-reproducible wherever they are encoded.
        """
        k = batch_count(delta, self.params)
        if k is not None:
            if slot is None:
                slots = None
            elif jnp.ndim(slot) == 0:
                # a scalar slot with a stacked batch broadcasts to the K
                # consecutive global slots starting there
                s0 = int(slot)
                if s0 < 0 or s0 + k > self.buffer_size:
                    raise ValueError(
                        f"scalar slot={s0} with a stacked batch of {k} "
                        f"rows names session slots {s0}..{s0 + k - 1}, "
                        f"outside the session's {self.buffer_size} slots; "
                        f"pass an explicit slot sequence or start lower")
                slots = list(range(s0, s0 + k))
            else:
                slots = list(slot)
            return self._encode_push_impl(delta, client_version,
                                          slots=slots)
        cps = self._encode_push_impl(
            jax.tree.map(lambda x: x[None], delta), client_version,
            slots=None if slot is None else [slot])
        return cps[0]

    def push_encoded(self, cp, rng=None) -> int:
        """The SERVER half of mask_mode='client': land one
        :class:`ClientPush` — or a list of them — in one jitted scatter.
        Returns the number of rows actually stored (duplicates and, under
        ``strict=False``, rejected pushes are counted-and-dropped)."""
        return self._push_encoded_impl(
            [cp] if isinstance(cp, ClientPush) else list(cp), rng=rng)

    # -- deprecated batch spellings (the unified entry points above accept
    # -- stacked pytrees directly) ------------------------------------------
    def push_batch(self, deltas, client_version, rng=None,
                   slots: Optional[Sequence[int]] = None) -> None:
        """Deprecated spelling of :meth:`push` on a stacked batch."""
        _warn_deprecated("push_batch", "push")
        self._push_impl(deltas, client_version, rng=rng, slots=slots)

    def encode_push_batch(self, deltas, client_version,
                          slots: Optional[Sequence[int]] = None
                          ) -> List[ClientPush]:
        """Deprecated spelling of :meth:`encode_push` on a stacked batch."""
        _warn_deprecated("encode_push_batch", "encode_push")
        return self._encode_push_impl(deltas, client_version, slots=slots)

    def push_encoded_batch(self, cps: Sequence[ClientPush],
                           rng=None) -> None:
        """Deprecated spelling of :meth:`push_encoded` on a list."""
        _warn_deprecated("push_encoded_batch", "push_encoded")
        self._push_encoded_impl(list(cps), rng=rng)

    # -- ingest implementations ---------------------------------------------
    def _encode_push_impl(self, deltas, client_version,
                          slots: Optional[Sequence[int]] = None
                          ) -> List[ClientPush]:
        """Encode a (K,)-stacked batch of deltas as the session's clients
        would — one vmapped jitted call, pure w.r.t. server state.  (This
        models CLIENT compute: in a fleet it runs on the devices, so it is
        central here only because the simulator stands in for them.)"""
        if self.mask_mode != "client":
            raise ValueError(
                f"encode_push is the client half of mask_mode='client' "
                f"(server is in mask_mode={self.mask_mode!r})")
        K = jax.tree.leaves(deltas)[0].shape[0]
        if slots is None:
            slots = self._take_slots(K)
        stals = self._staleness_of(client_version, K)
        with self._span("encode_push", k=K) as sp:
            rows, w, nrm, clipped = self._encode_batch(
                deltas, jnp.asarray(slots, jnp.int32), jnp.asarray(stals),
                self._session_key(),
                jax.random.fold_in(self._push_base, self.version))
            sp.fence(rows)
        self.telemetry.count(
            "upload_bytes", 4 * sum(int(r.size) for r in rows),
            lane=("packed" if self._spec.compression.identity
                  else "compressed"), **self._tl)
        # single-chunk pushes carry the bare packed (W,) word stream (the
        # legacy wire shape); multi-chunk pushes carry the per-chunk tuple
        row_of = ((lambda i: rows[0][i]) if len(rows) == 1
                  else (lambda i: tuple(r[i] for r in rows)))
        return [ClientPush(row_of(i), w[i], nrm[i], clipped[i],
                           float(stals[i]), self.version, int(s),
                           self._spec.field_modulus, self._new_token(),
                           self._spec.compression)
                for i, s in enumerate(slots)]

    def _push_encoded_impl(self, cps: Sequence[ClientPush],
                           rng=None) -> int:
        """Land a batch of already-masked rows in one scatter.

        Duplicate deliveries of tokened pushes are idempotent no-ops; a
        stale session or a conflicting/dead slot raises under
        ``strict=True`` and is counted-and-dropped under ``strict=False``
        (the rest of the batch still lands).  Returns the stored count.
        """
        if self.mask_mode != "client":
            raise ValueError(
                f"push_encoded is the server half of mask_mode='client' "
                f"(server is in mask_mode={self.mask_mode!r})")
        for cp in cps:
            if cp.modulus != self._spec.field_modulus:
                raise ValueError(
                    f"ClientPush packed for field modulus {cp.modulus} "
                    f"({sa.wire_bits(cp.modulus)}-bit wire) but the tier's "
                    f"session field is {self._spec.field_modulus} "
                    f"({sa.wire_bits(self._spec.field_modulus)}-bit): the "
                    "residue stream cannot be unpacked — client and tier "
                    "must agree on secure_agg_bits and the session size")
            if cp.compression != self._spec.compression:
                raise ValueError(
                    f"ClientPush encoded under compression "
                    f"{cp.compression.describe()} but the tier's session "
                    f"expects {self._spec.compression.describe()}: the row "
                    "lives in a different sketch domain and would decode "
                    "to garbage — client and tier must agree on "
                    "compress_mode and compress_rate for the session")
        kept: List[ClientPush] = []
        for cp in cps:
            if cp.token and cp.token in self._delivered_tokens:
                self.fault_metrics["duplicate_pushes"] += 1
                continue
            if cp.version != self.version:
                if self.strict:
                    raise ValueError(
                        f"stale ClientPush (session {cp.version} slot "
                        f"{cp.slot}; server at session {self.version}): the "
                        "pairwise mask no longer matches an open session "
                        "position")
                self.fault_metrics["rejected_pushes"] += 1
                continue
            kept.append(cp)
        slots = [cp.slot for cp in kept]
        if self.strict:
            self._check_slots(slots)
        else:
            seen: set = set()
            ok: List[ClientPush] = []
            for cp in kept:
                if cp.slot in seen or not self._slot_open(cp.slot):
                    self.fault_metrics["rejected_pushes"] += 1
                    continue
                seen.add(cp.slot)
                ok.append(cp)
            kept, slots = ok, [cp.slot for cp in ok]
        if not kept:
            return 0
        cps = kept
        stals = np.asarray([cp.staleness for cp in cps], np.float32)
        with self._span("push_encoded", k=len(cps)) as sp:
            idx, lsl, valid, st = self._route_by_leaf(slots, stals)
            crows = [cp.row if isinstance(cp.row, tuple) else (cp.row,)
                     for cp in cps]
            wrows = tuple(jnp.stack([cr[c] for cr in crows])
                          for c in range(self._plan.num_chunks))
            self.telemetry.count(
                "upload_bytes", 4 * sum(int(w_.size) for w_ in wrows),
                lane=("packed" if self._spec.compression.identity
                      else "compressed"), **self._tl)
            (self._bufs, self._wts, self._norms, self._clips,
             self._stal) = self._scatter_packed(
                self._bufs, self._wts, self._norms, self._clips, self._stal,
                wrows, idx, lsl, valid, st,
                jnp.stack([jnp.asarray(cp.weight) for cp in cps]),
                jnp.stack([jnp.asarray(cp.norm) for cp in cps]),
                jnp.stack([jnp.asarray(cp.clipped) for cp in cps]))
            sp.fence(self._bufs)
        for cp in cps:
            if cp.token:
                self._delivered_tokens.add(cp.token)
        self._mark(slots, rng)
        return len(cps)

    def _push_impl(self, deltas, client_version, rng=None,
                   slots: Optional[Sequence[int]] = None,
                   push_ids: Optional[Sequence[int]] = None) -> None:
        """Ingest a (K,)-stacked batch of raw deltas (see :meth:`push`)."""
        if self.mask_mode == "client":
            self._push_encoded_impl(
                self._encode_push_impl(deltas, client_version, slots=slots),
                rng=rng)
            return
        K = jax.tree.leaves(deltas)[0].shape[0]
        slot_of = None if slots is None else list(slots)
        pid_of = None if push_ids is None else list(push_ids)
        kept = list(range(K))
        if pid_of is not None:
            fresh = []
            for i in kept:
                if pid_of[i] is not None and pid_of[i] in self._delivered_tokens:
                    self.fault_metrics["duplicate_pushes"] += 1
                else:
                    fresh.append(i)
            kept = fresh
        if slot_of is not None:
            if self.strict:
                self._check_slots([slot_of[i] for i in kept])
            else:
                seen: set = set()
                ok = []
                for i in kept:
                    s = slot_of[i]
                    if s in seen or not self._slot_open(s):
                        self.fault_metrics["rejected_pushes"] += 1
                        continue
                    seen.add(s)
                    ok.append(i)
                kept = ok
        if not kept:
            return
        if len(kept) != K:
            sel = np.asarray(kept, np.int32)
            deltas = jax.tree.map(lambda x: x[sel], deltas)
            if jnp.ndim(client_version) != 0:
                client_version = np.asarray(client_version)[sel]
        if pid_of is not None:
            for i in kept:
                if pid_of[i] is not None:
                    self._delivered_tokens.add(pid_of[i])
        K = len(kept)
        slots = (self._take_slots(K) if slot_of is None
                 else [slot_of[i] for i in kept])
        stals = self._staleness_of(client_version, K)
        if self._enclave_bits:
            # enclave quantized wire: the rows the tier ingests are the
            # client-side stochastic quantization's reconstruction; the
            # packed word streams are what actually crossed the wire
            ekey = jax.random.fold_in(self._enclave_base, self._enclave_seq)
            self._enclave_seq += 1
            deltas, ewords = self._enclave_wire(deltas, ekey)
            self.telemetry.count(
                "upload_bytes", 4 * sum(int(w_.size) for w_ in ewords),
                lane="enclave", **self._tl)
        if not self._streaming:  # "tee": store raw rows, mask lane at flush
            with self._span("ingest", k=K, lane="raw") as sp:
                leaf, local = self._leaf_local(slots)
                self._bufs, self._stal, self._valid = self._scatter_raw(
                    self._bufs, self._stal, self._valid, leaf, local, deltas,
                    jnp.asarray(stals))
                sp.fence(self._bufs)
            self._mark(slots, rng)
            return
        with self._span("ingest", k=K, lane="stream") as sp:
            idx, lsl, valid, st = self._route_by_leaf(slots, stals)
            (self._bufs, self._wts, self._norms, self._clips,
             self._stal) = self._ingest_sharded(
                self._bufs, self._wts, self._norms, self._clips, self._stal,
                deltas, idx, lsl, valid, st, self._session_key(),
                jax.random.fold_in(self._push_base, self.version))
            sp.fence(self._bufs)
        self._mark(slots, rng)

    def _mark(self, slots, rng) -> None:
        for s in slots:
            self._present[s] = True
        self._fill += len(slots)
        self.telemetry.count("stored_contributions", len(slots), **self._tl)
        self.telemetry.gauge("buffered_contributions", self._fill,
                             **self._tl)
        # with dead leaves the session can never reach buffer_size, so the
        # deadline trigger is the LIVE capacity; _apply then routes through
        # the recovering flush step (dead slots are absent -> recovered)
        cap = self.live_capacity
        if cap > 0 and self._fill >= cap:
            self._apply(rng)

    def flush(self, rng=None, force: bool = False) -> bool:
        """Apply a partially-filled session (deadline / end of run) — the
        dropout-recovery path: leaf-local sweeps + root recovery in the
        session tree, the cross-shard edge sweep in the flat layout.

        Below ``FLConfig.flush_quorum`` (a fraction of the LIVE capacity —
        dead leaves leave the denominator) the flush ABSTAINS: nothing is
        decoded, contributions stay buffered, and
        ``fault_metrics['subquorum_deferrals']`` is bumped.  ``force=True``
        overrides.  Returns True when a params update was released."""
        if self._fill <= 0:
            return False
        with self._span("flush", forced=force, fill=self._fill):
            need = math.ceil(self.flush_quorum * max(self.live_capacity, 1))
            if not force and self._fill < need:
                self.fault_metrics["subquorum_deferrals"] += 1
                return False
            self._apply(rng)
        return True

    # -- server step --------------------------------------------------------
    def _apply(self, rng=None) -> None:
        if rng is None:  # deterministic per-version stream for rounding/noise
            rng = jax.random.fold_in(jax.random.PRNGKey(0xA5), self.version)
        L, Bl = self.num_leaves, self.leaf_buffer
        recovery = self._fill < self.buffer_size
        with self._span("decode", recovery=recovery, fill=self._fill) as sp:
            if self._streaming:
                present = jnp.asarray(
                    [1.0 if p else 0.0 for p in self._present],
                    jnp.float32).reshape(L, Bl)
                if not recovery:
                    step = self._step  # complete session: no recovery needed
                else:
                    if self._flush_step is None:
                        self._flush_step = self._build_flush_step()
                    step = self._flush_step  # dropout recovery
                self.params, self._opt_state, self.last_metrics = step(
                    self.params, self._opt_state, self._bufs, present,
                    self._wts, self._stal, self._norms, self._clips,
                    self._session_key(), rng)
            else:
                self.params, self._opt_state, self.last_metrics = self._step(
                    self.params, self._opt_state, self._bufs, self._stal,
                    self._valid, rng)
                self._valid = jnp.zeros_like(self._valid)
            sp.fence(self.params)
        self._present = [False] * self.buffer_size
        self.version += 1
        self._applied_updates += self._fill
        self.telemetry.count("aggregated_contributions", self._fill,
                             **self._tl)
        self.telemetry.gauge("buffered_contributions", 0, **self._tl)
        self._fill = 0
        self._dead_leaves.clear()  # restarted leaves join the new session
        self.fault_metrics["released_updates"] += 1
