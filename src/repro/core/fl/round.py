"""The synchronous DP-FL round step — the paper's training technique, jitted.

One round =
  1. every cohort client runs K local SGD steps on its on-device samples
     (the paper's regime: ~one sample per device, so per-client == per-example);
  2. each client's model delta is L2-clipped (DP-SGD) and, in ``device`` noise
     placement, locally noised;
  3. deltas are fixed-point quantized by the streamed engines' client
     encode (``aggregation.encode_plan_contribution``: PRF stochastic
     rounding, the fused Pallas kernel on a TPU) and summed with
     wraparound int32 arithmetic — bit-identical to the pairwise-masked
     secure-aggregation sum (masks cancel; see core/fl/secure_agg.py),
     lowering to one big integer all-reduce over the (pod, data) axes.
     With ``fl_cfg.secure_agg_masked`` the masks are real, not notional:
     every cohort slot adds its pairwise session mask (graph degree from
     ``fl_cfg.secure_agg_degree``) to its encoded rows inside the scan,
     and the round stays bit-identical because they cancel;
  4. in ``tee`` placement, Gaussian noise is added once to the decoded
     aggregate inside the trusted boundary;
  5. the server optimizer applies the noised mean delta to the global model.

Two execution strategies over the cohort:
  - ``client_parallel=True``: clients sharded over the `data` mesh axis,
    vmapped grad per chunk — fast path for models whose full per-client delta
    fits per-device (<~8B params with TP16).
  - ``client_parallel=False``: sequential scan over clients; each client's
    single sequence is itself sharded (sequence/FSDP parallelism) so the
    per-client delta is fully 2-D sharded — required for the >=16B archs.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import telemetry as tele
from repro.core.fl import aggregation as agg
from repro.core.fl.server_opt import build_server_opt
from repro.kernels.ops import on_tpu


class FLState(NamedTuple):
    params: Any
    opt_state: Any
    round_idx: jnp.ndarray  # int32 scalar


def init_fl_state(params, fl_cfg) -> FLState:
    opt = build_server_opt(fl_cfg)
    return FLState(params, opt.init(params), jnp.zeros((), jnp.int32))


def build_client_update(loss_fn: Callable, fl_cfg) -> Callable:
    """client_update(params, client_batch, rng) -> (delta_f32, first_loss).

    With ``fl_cfg.fedprox_mu > 0`` each local step descends the FedProx
    objective (Li et al. 2020): the gradient gains the proximal pull
    ``mu * (w - w_round)`` toward the round-start model, bounding client
    drift under non-IID data / stale async pulls.  ``mu = 0`` traces the
    exact legacy computation (bit-identical).
    """
    K, lr = fl_cfg.local_steps, fl_cfg.local_lr
    mu = float(getattr(fl_cfg, "fedprox_mu", 0.0))

    def client_update(params, cbatch, rng):
        del rng  # local data order is fixed (single sample per device)

        def one_step(p, _):
            loss, g = jax.value_and_grad(
                lambda q: loss_fn(q, cbatch)[0])(p)
            if mu > 0.0:
                g = jax.tree.map(
                    lambda gi, pi, p0: gi.astype(jnp.float32)
                    + mu * (pi.astype(jnp.float32)
                            - p0.astype(jnp.float32)),
                    g, p, params)
            p2 = jax.tree.map(
                lambda a, b: (a.astype(jnp.float32) - lr * b.astype(jnp.float32)
                              ).astype(a.dtype), p, g)
            return p2, loss

        pK, losses = jax.lax.scan(one_step, params, None, length=K)
        delta = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), pK, params)
        return delta, losses[0]

    return client_update


def build_scaffold_client_update(loss_fn: Callable, fl_cfg) -> Callable:
    """SCAFFOLD local training (Karimireddy et al. 2020, option II).

    Returns ``client_update(params, c_server, c_client, cbatch, rng) ->
    ((delta_x, delta_c), first_loss)``: K local steps along the
    variance-corrected direction ``g - c_client + c_server``, then the
    option-II control-variate refresh

        c_client+ = c_client - c_server - delta_x / (K * lr)

    reported as ``delta_c = c_client+ - c_client`` so both deltas travel
    the same pytree push channel (``simulate_training`` stacks them as
    ``{'x': ..., 'c': ...}``).  With both variates zero the model delta is
    bit-identical to :func:`build_client_update` at ``fedprox_mu = 0``.
    """
    K, lr = fl_cfg.local_steps, fl_cfg.local_lr

    def client_update(params, c_server, c_client, cbatch, rng):
        del rng  # local data order is fixed (single sample per device)

        def one_step(p, _):
            loss, g = jax.value_and_grad(
                lambda q: loss_fn(q, cbatch)[0])(p)
            p2 = jax.tree.map(
                lambda a, b, cs, cc: (a.astype(jnp.float32)
                                      - lr * (b.astype(jnp.float32) - cc + cs)
                                      ).astype(a.dtype),
                p, g, c_server, c_client)
            return p2, loss

        pK, losses = jax.lax.scan(one_step, params, None, length=K)
        delta = jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), pK,
            params)
        delta_c = jax.tree.map(
            lambda cs, d: -cs - d / (K * lr), c_server, delta)
        return (delta, delta_c), losses[0]

    return client_update


# ---------------------------------------------------------------------------
# Fixed-point secure-aggregation encoding lives in the shared engine
# (core/fl/aggregation.py); the historical name of the scale stays.
# ---------------------------------------------------------------------------
_sa_scale = agg.fixed_point_scale


def _contribution_fn(client_update: Callable, spec, plan, sessions, *,
                     masked: bool):
    """One client's round contribution, shared by both round builders.

    ``contribute(params, cbatch, rng, weight, slot) -> (contribution,
    loss, pre-clip norm, clipped)``.  Under secure aggregation the
    contribution is the streamed engines' client encode
    (``aggregation.encode_plan_contribution``): clip, weight, device
    noise, PRF stochastic rounding and, when ``masked``, the slot's
    pairwise mask, as per-chunk int32 rows of the ``ParamPlan``.  Masked
    and unmasked rounds consume the same uniforms, so their modular sums
    are bit-identical.  On a TPU the encode runs the fused Pallas kernel;
    elsewhere its jnp twin, bit-identical to it.  Without secure
    aggregation the contribution is the clipped, weighted f32 delta.
    """
    use_pallas = on_tpu()

    def contribute(params, cbatch, rng, w, slot):
        delta, loss = client_update(params, cbatch, rng)
        if spec.use_secure_agg:
            rows, nrm, clipped = agg.encode_plan_contribution(
                delta, w, slot, spec, plan, sessions, rng, masked=masked,
                use_pallas=use_pallas)
            return rows, loss, nrm, clipped
        delta, nrm, clipped = agg.privatize_contribution(delta, spec, rng)
        return (jax.tree.map(lambda d: d * w, delta), loss, nrm,
                clipped.astype(jnp.float32))

    return contribute


def _finalize(acc, w_s, spec, plan, rng):
    """Decode + weight-normalize + TEE noise: the shared engine semantics."""
    rng = jax.random.fold_in(rng, 0xDEE)
    if spec.use_secure_agg:
        return agg.finalize_plan_aggregate(acc, w_s, spec, plan, rng)
    return agg.finalize_aggregate(acc, w_s, spec, rng)


# ---------------------------------------------------------------------------
def build_round_step(loss_fn: Callable, fl_cfg, *, cohort_size: int,
                     client_parallel: bool = True,
                     clients_per_chunk: int = 0,
                     telemetry: Optional["tele.Telemetry"] = None) -> Callable:
    """Returns round_step(state, batch, rng) -> (state, metrics).

    batch: pytree whose leaves have leading axis `cohort_size`
           (per-client on-device data), plus optional 'weight' (cohort,)
           from the Orchestrator's sample-submission control.

    Each chunk of ``clients_per_chunk`` clients is vmapped through
    :func:`_contribution_fn` (the streamed engines' client encode) and
    modular-summed into per-ParamPlan-chunk int32 accumulators.

    The returned step is instrumented with ``round.execute`` spans on the
    ``telemetry`` registry (the process default when None).  The span label
    is a host-side call counter, never a traced value — callers are free to
    ``jax.jit`` the returned function (spans then record at trace time
    only, which is what a jitted replay can observe anyway).
    """
    tel = telemetry if telemetry is not None else tele.get_default()
    with tel.span("round.setup", kind="sync", cohort=cohort_size):
        client_update = build_client_update(loss_fn, fl_cfg)
        server = build_server_opt(fl_cfg)
        spec = agg.make_spec(fl_cfg, cohort_size)
        masked = spec.use_secure_agg and getattr(fl_cfg, "secure_agg_masked",
                                                 False)

        if clients_per_chunk <= 0:
            clients_per_chunk = cohort_size if client_parallel else 1
        m = clients_per_chunk
        assert cohort_size % m == 0
        n_chunks = cohort_size // m

    def round_step(state: FLState, batch, rng):
        params = state.params
        weights = batch.get("weight")
        if weights is None:
            weights = jnp.ones((cohort_size,), jnp.float32)
        batch = {k: v for k, v in batch.items() if k != "weight"}
        # reshape cohort -> (n_chunks, m, ...).  The (m, n_chunks)-then-swap
        # order keeps a cohort axis that is block-sharded m-ways aligned with
        # the chunk's client axis — no resharding collective is needed.
        cbatches = jax.tree.map(
            lambda x: x.reshape((m, n_chunks) + x.shape[1:]).swapaxes(0, 1), batch)
        wchunks = weights.reshape(m, n_chunks).swapaxes(0, 1)
        rngs = jax.random.split(rng, n_chunks * m).reshape(n_chunks, m, 2)
        # pairwise-mask session: one per round, slot = position in the cohort
        # (any bijection works — only slot uniqueness matters for cancellation)
        slots = jnp.arange(cohort_size, dtype=jnp.int32).reshape(
            m, n_chunks).swapaxes(0, 1)
        # pairwise-mask sessions of the round: one MaskSession per ParamPlan
        # chunk (the single-chunk plan = the legacy one-session round).  Each
        # chunk's graph permutation is derived from its session key, so every
        # cohort chunk's mask shares one consistent graph per plan chunk —
        # cancellation needs it.
        plan = agg.plan_for(params, fl_cfg)
        sessions = agg.plan_sessions(
            spec, plan, jax.random.fold_in(rng, 0x5E55)) if masked else None
        contribute = jax.vmap(
            _contribution_fn(client_update, spec, plan, sessions,
                             masked=masked),
            in_axes=(None, 0, 0, 0, 0))

        deferred = getattr(fl_cfg, "deferred_agg", False) and m > 1
        # per-client-slot partial accumulators under deferred_agg: the slot
        # axis shards like the client axis, so the chunk-scan accumulation
        # is collective-free and the cross-device reduction happens ONCE
        # after the scan.
        lead = (m,) if deferred else ()
        if spec.use_secure_agg:  # per-chunk int32 rows of the ParamPlan
            acc0 = tuple(jnp.zeros(lead + (ck.padded,), jnp.int32)
                         for ck in plan.chunks)
        else:
            acc0 = agg.zero_accumulator(params, spec, leading=lead)
        stats0 = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), jnp.zeros(()))

        def chunk_body(carry, xs):
            acc, (loss_s, norm_s, clip_s, w_s) = carry
            cbatch, crng, w, cslot = xs
            encs, losses, nrms, clips = contribute(params, cbatch, crng, w,
                                                   cslot)
            # int32 rows wrap mod 2^32: the masked sum equals the unmasked
            acc = jax.tree.map(lambda a, e: a + (e if deferred else e.sum(0)),
                               acc, encs)
            stats = (loss_s + (losses * w).sum(), norm_s + (nrms * w).sum(),
                     clip_s + (clips * w).sum(), w_s + w.sum())
            return (acc, stats), None

        (acc, (loss_s, norm_s, clip_s, w_s)), _ = jax.lax.scan(
            chunk_body, (acc0, stats0), (cbatches, rngs, wchunks, slots))

        w_total = jnp.maximum(w_s, 1e-9)
        if deferred:
            acc = jax.tree.map(lambda a: a.sum(0), acc)  # one reduction/round
        mean_delta = _finalize(acc, w_s, spec, plan, rng)

        new_params, new_opt = server.apply(params, state.opt_state, mean_delta)
        metrics = {
            "loss": loss_s / w_total,
            "update_norm": norm_s / w_total,
            "clip_fraction": clip_s / w_total,
            "participation": w_s / cohort_size,
            "round": state.round_idx,
        }
        return FLState(new_params, new_opt, state.round_idx + 1), metrics

    return _instrument_step(round_step, tel, "sync")


def _instrument_step(round_step: Callable, tel: "tele.Telemetry",
                     kind: str) -> Callable:
    """Wrap a round step with ``round.execute`` spans.

    The ``call`` label is a host-side counter — NOT ``state.round_idx`` —
    so the wrapper never reads a traced value (it must survive being
    jitted by the caller)."""
    calls = itertools.count()

    def instrumented_round_step(state, batch, rng):
        with tel.span("round.execute", kind=kind, call=next(calls)) as sp:
            out = round_step(state, batch, rng)
            sp.fence(out)
        return out

    return instrumented_round_step


# ---------------------------------------------------------------------------
# Cohort-sharded synchronous rounds — the aggregation tier's sync path
# ---------------------------------------------------------------------------
def build_sharded_round_step(loss_fn: Callable, fl_cfg, *, cohort_size: int,
                             num_leaves: int, mesh=None,
                             telemetry: Optional["tele.Telemetry"] = None
                             ) -> Callable:
    """A synchronous round sharded over the aggregation tier's leaf mesh.

    The cohort splits into ``num_leaves`` contiguous shards; each leaf
    trains its ``cohort_size / num_leaves`` clients (vmapped), clips,
    encodes (+ adds each GLOBAL slot's pairwise session mask under
    ``fl_cfg.secure_agg_masked`` — one session spans the whole cohort, so
    masks pair ACROSS leaves) and modular-sums a per-leaf partial; the root
    combines partials with one field-modulus ``psum`` (int32, mod 2^32),
    decodes, draws central noise once, and applies the server optimizer.

    Because the int32 accumulation is exact, the masked sharded round is
    BIT-identical to the unmasked sharded round (cross-leaf masks cancel
    through the psum) — the same guarantee the single-host round makes,
    test-enforced.  Per-client keys follow the fully-vmapped single-chunk
    schedule (``split(rng, cohort_size)``), so per-client arithmetic
    matches ``build_round_step(clients_per_chunk=cohort_size)``.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import LEAF_AXIS, make_agg_mesh

    tel = telemetry if telemetry is not None else tele.get_default()
    with tel.span("round.setup", kind="sharded", cohort=cohort_size,
                  leaves=num_leaves):
        assert cohort_size % num_leaves == 0
        m = cohort_size // num_leaves
        client_update = build_client_update(loss_fn, fl_cfg)
        server = build_server_opt(fl_cfg)
        spec = agg.make_spec(fl_cfg, cohort_size)
        if not spec.use_secure_agg:
            raise ValueError("the sharded tier aggregates in the secure-agg "
                             "integer field: set secure_agg_bits > 0")
        masked = getattr(fl_cfg, "secure_agg_masked", False)
        if mesh is None:
            mesh = make_agg_mesh(num_leaves)

    def round_step(state: FLState, batch, rng):
        params = state.params
        weights = batch.get("weight")
        if weights is None:
            weights = jnp.ones((cohort_size,), jnp.float32)
        batch = {k: v for k, v in batch.items() if k != "weight"}
        rngs = jax.random.split(rng, cohort_size)  # client c -> rngs[c]
        skey = jax.random.fold_in(rng, 0x5E55) if masked else None
        plan = agg.plan_for(params, fl_cfg)

        def leaf_fn(params, cbatch_l, rngs_l, w_l, *mask_args):
            # every leaf derives the SAME per-chunk sessions (incl. the
            # random k-regular graphs) from the replicated session key —
            # no permutation array needs threading through shard_map
            sessions = agg.plan_sessions(spec, plan, mask_args[0]) \
                if masked else None
            slots = (jax.lax.axis_index(LEAF_AXIS) * m
                     + jnp.arange(m, dtype=jnp.int32))
            contribute = _contribution_fn(client_update, spec, plan,
                                          sessions, masked=masked)
            rows, losses, nrms, clips = jax.vmap(
                contribute, in_axes=(None, 0, 0, 0, 0))(
                    params, cbatch_l, rngs_l, w_l, slots)
            # the root combine: ONE integer all-reduce per chunk and round
            acc = tuple(jax.lax.psum(r.sum(0), LEAF_AXIS) for r in rows)
            stats = tuple(
                jax.lax.psum(s, LEAF_AXIS)
                for s in ((losses * w_l).sum(), (nrms * w_l).sum(),
                          (clips * w_l).sum(), w_l.sum()))
            return acc, stats

        args = [params, batch, rngs, weights]
        in_specs = [P(), P(LEAF_AXIS), P(LEAF_AXIS), P(LEAF_AXIS)]
        if masked:
            args.append(skey)
            in_specs.append(P())
        acc, (loss_s, norm_s, clip_s, w_s) = jax.shard_map(
            leaf_fn, mesh=mesh, in_specs=tuple(in_specs),
            out_specs=(P(), (P(), P(), P(), P())), check_vma=False,
        )(*args)

        w_total = jnp.maximum(w_s, 1e-9)
        mean_delta = _finalize(acc, w_s, spec, plan, rng)
        new_params, new_opt = server.apply(params, state.opt_state,
                                           mean_delta)
        metrics = {
            "loss": loss_s / w_total,
            "update_norm": norm_s / w_total,
            "clip_fraction": clip_s / w_total,
            "participation": w_s / cohort_size,
            "round": state.round_idx,
        }
        return FLState(new_params, new_opt, state.round_idx + 1), metrics

    return _instrument_step(jax.jit(round_step), tel, "sharded")


def rounds_to_epsilon(fl_cfg, cohort_size: int, population: int, rounds: int) -> float:
    """Convenience wrapper over the RDP accountant (see accountant.py)."""
    from repro.core.fl.accountant import compute_epsilon
    q = cohort_size / population
    return compute_epsilon(q, fl_cfg.noise_multiplier, rounds, fl_cfg.dp_delta)
