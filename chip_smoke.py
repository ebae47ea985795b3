"""On-chip smoke test of the aggregation service's main path.

Drives whisper-tiny at full width (``registry.get_config("whisper-tiny",
reduced=False)``: 39,593,856 parameters in 110 leaves, random weights from
``--seed``) through the engines a deployment calls, on one TPU:

  client   local SGD steps of the whisper loss for a few clients (real
           pytree deltas, synthetic token streams and frame embeddings;
           XLA's matmuls, no Pallas kernel);
  fedbuff  ``AsyncServer`` in each mask mode (streamed ``off``, ``client``,
           ``tee_stream``; batched ``off`` and ``tee``): a full flush, then
           a partial flush with one slot absent (dropout recovery in the
           masked modes).  Released params are checked against a plain f32
           survivor-sum reference, and the masked modes bit-for-bit against
           their unmasked twin;
  sync     two DP rounds of ``build_round_step`` (the path of
           ``python -m repro.launch.train --arch whisper-tiny --full``),
           masked bit-for-bit against unmasked, and the round's client
           encode (Pallas) bit-for-bit against its jnp twin, which the
           CPU tests of the round run;
  sketch   the rotation-sketch upload (``compress_mode="sketch"``, rate
           0.25) through ``rotate_quantize_prf``, bit-for-bit against the
           jnp encode, then a session released with one slot absent.

``--four-chips`` runs only the tier, on seeded random deltas:
``ShardedAsyncServer(two_level=True)`` with one leaf per chip, a whole leaf
killed before the flush, checked bit-for-bit against the single-host
``AsyncServer`` on the surviving pushes and against the f32 reference.

Every phase prints one line: its result, ``use_pallas``, the number of
Pallas kernels (``tpu_custom_call``) in the compiled programs it checks
(asserted > 0 in every phase whose engine runs Pallas: all but client),
compile and wall seconds, and each error beside its bound.  The last line
of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits non-zero and prints no result.

  python3 chip_smoke.py [--seed N] [--four-chips]
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

U = 2.0 ** -24  # f32 unit roundoff
KERNEL = 'custom_call_target="tpu_custom_call"'
BUFFER = 4  # FedBuff slots per session
SEQ_LEN = 16  # decoder tokens per client sample


class PhaseFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


def kernels_in(fn, *args) -> tuple:
    """(Pallas kernel calls in the compiled program, compile seconds)."""
    t = time.perf_counter()
    text = fn.lower(*args).compile().as_text()
    return text.count(KERNEL), time.perf_counter() - t


def host(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def bit_equal(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(host(a), host(b)))


def line(name: str, **fields) -> str:
    parts = []
    for k, v in fields.items():
        if isinstance(v, float):
            v = f"{v:.4g}" if k.endswith(("err", "bound", "ratio")) \
                else f"{v:.2f}"
        parts.append(f"{k}={v}")
    return f"phase {name}: PASS " + " ".join(parts)


# ---------------------------------------------------------------------------
# Set-up: the model, its random weights and the clients' data, from a seed
# ---------------------------------------------------------------------------
def make_setup(cfg, seed: int, n_clients: int):
    """Model, params and per-client batches (all generated from ``seed``)."""
    from repro.data.synthetic import fl_token_batch
    from repro.models.model import build_model

    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init)(key)
    toks = fl_token_batch(n_clients, SEQ_LEN, cfg.vocab_size, seed=seed)
    batch = {k: jnp.asarray(v) for k, v in toks.items()}
    batch["audio_embeds"] = 0.02 * jax.random.normal(
        jax.random.fold_in(key, 1),
        (n_clients, 1, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return model, params, batch


def fl_config(**kw):
    from repro.configs.base import FLConfig
    base = dict(local_steps=2, local_lr=0.05, clip_norm=1.0,
                noise_multiplier=0.0, secure_agg_bits=32, server_lr=1.0)
    base.update(kw)
    return FLConfig(**base)


# ---------------------------------------------------------------------------
# Phase 1: clients train locally on the device
# ---------------------------------------------------------------------------
def client_phase(model, params, batch, fl):
    """Local SGD on every client (``round.build_client_update``, vmapped).
    Returns (deltas scaled into the clip ball, phase fields).

    Local training is XLA's own matmuls: no Pallas kernel runs here, and
    the phase reports the count it finds (0).  The clients' upload encode
    runs the kernels in the fedbuff and sync phases.  The FedBuff
    reference needs no norm kernel: each delta is scaled to half the clip
    norm, so the engines' clip is the identity whatever order their f32
    norm reduction takes (clipping itself runs in the sync DP phase).
    """
    from repro.core.fl.round import build_client_update

    t0 = time.perf_counter()
    update = jax.jit(jax.vmap(build_client_update(model.loss_fn, fl),
                              in_axes=(None, 0, None)))
    rng = jax.random.PRNGKey(0)
    n_kern, compile_s = kernels_in(update, params, batch, rng)
    deltas, losses = update(params, batch, rng)
    losses = np.asarray(losses)
    norms = np.sqrt(sum(np.asarray(jnp.sum(x.reshape(x.shape[0], -1) ** 2,
                                           axis=1))
                        for x in jax.tree.leaves(deltas)))
    check(np.all(np.isfinite(losses)), f"client losses not finite: {losses}")
    check(np.all(np.isfinite(norms)) and np.all(norms > 0),
          f"client delta norms not finite/positive: {norms}")
    for d, p in zip(jax.tree.leaves(deltas), jax.tree.leaves(params)):
        check(d.shape[1:] == p.shape, "delta leaf shape != param leaf shape")
    scale = jnp.asarray(0.5 * fl.clip_norm / norms, jnp.float32)
    deltas = jax.tree.map(
        lambda x: x * scale.reshape((-1,) + (1,) * (x.ndim - 1)), deltas)
    n = len(losses)
    out = [jax.tree.map(lambda x, i=i: x[i], deltas) for i in range(n)]
    fields = dict(use_pallas=False, tpu_custom_call=n_kern, clients=n,
                  loss=float(losses.mean()), delta_norm=float(norms.mean()),
                  compile_s=compile_s, wall_s=time.perf_counter() - t0)
    return out, fields


def random_deltas(params, fl, n: int, seed: int):
    """``n`` seeded Gaussian deltas shaped like ``params``, each scaled to
    half the clip norm (the tier run's contributions: it checks the tier,
    not local training)."""
    out = []
    for i in range(n):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), 1000 + i)
        leaves, treedef = jax.tree.flatten(params)
        d = [jax.random.normal(jax.random.fold_in(key, j), x.shape)
             for j, x in enumerate(leaves)]
        nrm = jnp.sqrt(sum(jnp.sum(x * x) for x in d))
        out.append(jax.tree.unflatten(
            treedef, [x * (0.5 * fl.clip_norm / nrm) for x in d]))
    return out


# ---------------------------------------------------------------------------
# The f32 survivor-sum reference and its error bound
# ---------------------------------------------------------------------------
@jax.jit
def _reference(params, survivors):
    """params + (sum of survivor deltas) / n, in plain f32, left to right."""
    n = len(survivors)
    total = survivors[0]
    for d in survivors[1:]:
        total = jax.tree.map(jnp.add, total, d)
    absum = jax.tree.map(jnp.abs, survivors[0])
    for d in survivors[1:]:
        absum = jax.tree.map(lambda a, x: a + jnp.abs(x), absum, d)
    ref = jax.tree.map(lambda p, t: p + t / n, params, total)
    return ref, absum


@jax.jit
def _err_over_bound(got, ref, absum, n, step):
    """max |got - ref| and max |got - ref| / bound, elementwise.

    Derivation (per element, unit roundoff u = 2^-24, n survivors of unit
    weight, fixed-point step 1/s = 1/sa_scale, S = sum_i |x_i|):

    engine: each contribution is x*1 (clip and weight are exactly 1)
      rounded once, times s rounded once (<= 2u|x_i| in the value), then
      stochastically rounded to an integer (< 1 step).  The integer sum is
      exact; its f32 conversion, /s and /n round three times (<= 3u on the
      mean).  So |mean_eng - mean| < n/(s n) + 2u S/n + 3u S/n.
    reference: n-1 f32 adds and one divide: <= (n-1)u S/n + u S/n.
    params: got = fl(p + mean_eng) and ref = fl(p + mean_ref) each round
      once, by at most half the spacing of f32 at magnitude <= M =
      |ref| + the mean bound, so together by at most spacing(M).

    bound = step + (n + 5) u S / n, times gamma's 1.01 for the second-order
    terms (n u << 0.01), + spacing(|ref| + that).
    """
    leaves = []
    for g, r, a in zip(jax.tree.leaves(got), jax.tree.leaves(ref),
                       jax.tree.leaves(absum)):
        mean_bound = 1.01 * (step + (n + 5) * U * a / n)
        bound = mean_bound + jnp.spacing(jnp.abs(r) + mean_bound)
        err = jnp.abs(g - r)
        leaves.append((jnp.max(err), jnp.max(err / bound)))
    return (jnp.max(jnp.stack([e for e, _ in leaves])),
            jnp.max(jnp.stack([q for _, q in leaves])))


def check_reference(tag, got, params_before, survivors, sa_scale):
    """Compare released params with the f32 reference; returns fields."""
    ref, absum = _reference(params_before, tuple(survivors))
    n = len(survivors)
    err, ratio = _err_over_bound(got, ref, absum, float(n),
                                 float(1.0 / np.float32(sa_scale)))
    err, ratio = float(err), float(ratio)
    check(np.isfinite(ratio) and ratio <= 1.0,
          f"{tag}: released params off the f32 survivor reference "
          f"(max err {err:.4g}, {ratio:.4g} x the bound)")
    return {f"{tag}_max_err": err, f"{tag}_err_over_bound": ratio}


# ---------------------------------------------------------------------------
# Phase 2: FedBuff sessions in every mask mode
# ---------------------------------------------------------------------------
def fedbuff_phase(params, deltas, fl, mode: str, *, use_pallas: bool,
                  stream_encode=None, expect_kernels: bool):
    """Two flushes of one ``AsyncServer``: a full session, then one with
    the last slot absent.  Returns (released params per flush, fields)."""
    from repro.core.fl.async_fl import AsyncServer

    t0 = time.perf_counter()
    srv = AsyncServer(params, fl, buffer_size=BUFFER, mask_mode=mode,
                      staleness_mode="constant", use_pallas=use_pallas,
                      stream_encode=stream_encode)
    scale = srv._spec.sa_scale
    fields, released = {}, []
    # count the kernels of the program that holds them (compiled ahead of
    # the engine's own first call, with the same argument types)
    if srv._streaming:  # the per-arrival encode
        n_kern, compile_s = kernels_in(srv._masked_encode, deltas[0], 0, 0,
                                       srv._session_key(),
                                       jax.random.PRNGKey(0))
    else:  # the batched flush
        n_kern, compile_s = kernels_in(srv._step, srv.params, srv._opt_state,
                                       srv._bufs, srv._stal, srv._valid,
                                       jax.random.PRNGKey(0))
    for flush, k in enumerate((BUFFER, BUFFER - 1)):
        before = srv.params
        for i in range(k):
            srv.push(deltas[i % len(deltas)], srv.version)
        if k < BUFFER:
            check(srv.flush(force=True), f"{mode}: partial flush refused")
        check(srv.version == flush + 1, f"{mode}: flush {flush} not released")
        fields.update(check_reference(
            f"flush{flush}", srv.params, before,
            [deltas[i % len(deltas)] for i in range(k)], scale))
        released.append(srv.params)
    if expect_kernels:
        check(n_kern > 0, f"{mode}: no Pallas kernel in the compiled step")
    head = dict(use_pallas=use_pallas, tpu_custom_call=n_kern)
    return released, dict(head, **fields, compile_s=compile_s,
                          wall_s=time.perf_counter() - t0)


def fedbuff_phases(params, deltas, fl, *, use_pallas, expect_kernels, emit):
    """All mask modes; the masked ones bit-for-bit against their twin."""
    runs = {}
    for name, mode, stream in (("off", "off", None),
                               ("client", "client", None),
                               ("tee_stream", "tee_stream", None),
                               ("off_batched", "off", False),
                               ("tee", "tee", None)):
        runs[name], fields = fedbuff_phase(
            params, deltas, fl, mode, use_pallas=use_pallas,
            stream_encode=stream, expect_kernels=expect_kernels)
        emit(line(f"fedbuff_{name}", **fields))
    for masked, twin in (("client", "off"), ("tee_stream", "off"),
                         ("tee", "off_batched")):
        same = all(bit_equal(a, b) for a, b in zip(runs[masked], runs[twin]))
        check(same, f"{masked} released params differ from {twin}")
        emit(f"bitcmp {masked} == {twin}: True")


# ---------------------------------------------------------------------------
# Phase 3: synchronous DP rounds
# ---------------------------------------------------------------------------
def encode_twins(params, deltas, fl):
    """The sync round's client encode (``encode_plan_contribution``,
    masked, clipping: the deltas are scaled to twice the clip norm, and
    weighted) through the Pallas kernel and through its jnp twin, which
    the CPU tests of the round run.  Returns True if they agree to the
    bit on every client's rows."""
    import dataclasses

    from repro.core.fl import aggregation as agg

    cfg = dataclasses.replace(fl, secure_agg_masked=True)
    n = len(deltas)
    spec, plan = agg.make_spec(cfg, n), agg.plan_for(params, cfg)
    stacked = jax.tree.map(lambda *x: 4.0 * jnp.stack(x), *deltas)

    @functools.partial(jax.jit, static_argnums=0)
    def encode(pallas, ds):
        sessions = agg.plan_sessions(spec, plan, jax.random.PRNGKey(7))
        rngs = jax.random.split(jax.random.PRNGKey(8), n)
        return jax.vmap(lambda d, r, s: agg.encode_plan_contribution(
            d, 0.75, s, spec, plan, sessions, r, masked=True,
            use_pallas=pallas)[0])(ds, rngs, jnp.arange(n, dtype=jnp.int32))

    return bit_equal(encode(True, stacked), encode(False, stacked))


def sync_phase(model, params, batch, deltas, fl, *, expect_kernels: bool,
               rounds: int = 2):
    """DP rounds (central noise, clipping) masked vs unmasked, to the bit,
    and the round's client encode against its jnp twin on ``deltas``.
    The round picks its encode path itself (the Pallas kernel on a TPU)."""
    import dataclasses

    from repro.core.fl import round as fl_round
    from repro.core.fl.round import build_round_step, init_fl_state

    t0 = time.perf_counter()
    use_pallas = fl_round.on_tpu()
    cohort = jax.tree.leaves(batch)[0].shape[0]
    fl = dataclasses.replace(fl, noise_multiplier=0.3, noise_placement="tee")
    states, steps, n_kern, compile_s = {}, {}, 0, 0.0
    for masked in (False, True):
        cfg = dataclasses.replace(fl, secure_agg_masked=masked)
        steps[masked] = jax.jit(build_round_step(
            model.loss_fn, cfg, cohort_size=cohort))
        states[masked] = init_fl_state(params, cfg)
    n_kern, compile_s = kernels_in(steps[True], states[True], batch,
                                   jax.random.PRNGKey(0))
    if expect_kernels:
        check(n_kern > 0, "sync: no Pallas kernel in the compiled round")
    fields = dict(use_pallas=use_pallas, tpu_custom_call=n_kern)
    for r in range(rounds):
        rng = jax.random.PRNGKey(100 + r)
        metrics = {}
        for masked in (False, True):
            states[masked], metrics[masked] = steps[masked](
                states[masked], batch, rng)
        loss = float(metrics[True]["loss"])
        check(np.isfinite(loss), f"sync round {r}: loss {loss}")
        check(all(np.all(np.isfinite(x)) for x in host(states[True].params)),
              f"sync round {r}: params not finite")
        check(bit_equal(states[True].params, states[False].params),
              f"sync round {r}: masked params differ from unmasked")
        fields[f"round{r}_loss"] = loss
        fields[f"round{r}_clip_fraction"] = float(
            metrics[True]["clip_fraction"])
        fields[f"round{r}_masked_eq_unmasked"] = True
    if use_pallas:
        check(encode_twins(params, deltas, fl),
              "sync: Pallas client encode differs from the jnp encode")
    fields["encode_pallas_eq_jnp"] = use_pallas
    return dict(fields, compile_s=compile_s,
                wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# Phase 4: the rotation-sketch upload
# ---------------------------------------------------------------------------
def sketch_phase(params, deltas, fl, *, use_pallas: bool,
                 expect_kernels: bool):
    """The sketch wire: the fused encode bit-for-bit against the jnp
    encode, then a tee_stream session released with one slot absent."""
    import dataclasses

    from repro.core.fl.async_fl import AsyncServer

    t0 = time.perf_counter()
    fl = dataclasses.replace(fl, compress_mode="sketch", compress_rate=0.25)
    srv = {p: AsyncServer(params, fl, buffer_size=BUFFER,
                          mask_mode="tee_stream", staleness_mode="constant",
                          use_pallas=p)
           for p in (use_pallas, False)}
    args = (deltas[0], 0, 0, srv[False]._session_key(),
            jax.random.PRNGKey(0))
    n_kern, compile_s = kernels_in(srv[use_pallas]._masked_encode, *args)
    if expect_kernels:
        check(n_kern > 0, "sketch: no Pallas kernel in the compiled encode")
    rows = {p: s_._masked_encode(*args)[0] for p, s_ in srv.items()}
    check(bit_equal(rows[use_pallas], rows[False]),
          "sketch: Pallas encode differs from the jnp encode")
    one = srv[use_pallas]
    for i in range(BUFFER - 1):
        one.push(deltas[i % len(deltas)], one.version)
    check(one.flush(force=True) and one.version == 1,
          "sketch: partial session not released")
    check(all(np.all(np.isfinite(x)) for x in host(one.params)),
          "sketch: params not finite")
    wire = sum(int(r.size) for r in rows[False])
    return dict(use_pallas=use_pallas, tpu_custom_call=n_kern, rate=0.25,
                wire_words=wire, pallas_eq_jnp=True, compile_s=compile_s,
                wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# --four-chips: the two-level tier with one leaf per chip
# ---------------------------------------------------------------------------
def tier_phase(params, deltas, fl, *, use_pallas: bool, expect_kernels: bool,
               num_leaves: int = 4, leaf_buffer: int = 2, dead_leaf: int = 2,
               mode: str = "tee_stream", emit=print):
    """A session tree over ``num_leaves`` chips loses a whole leaf before
    its flush; the release must equal the single host's on the survivors."""
    from repro.core.fl.async_fl import AsyncServer
    from repro.core.fl.hierarchy import ShardedAsyncServer

    t0 = time.perf_counter()
    B = num_leaves * leaf_buffer
    tier = ShardedAsyncServer(params, fl, num_leaves=num_leaves,
                              leaf_buffer=leaf_buffer, mask_mode=mode,
                              staleness_mode="constant", two_level=True,
                              use_pallas=use_pallas)
    leaf_dev = {}
    for shard in tier._bufs[0].addressable_shards:
        leaf_dev[shard.index[0].start or 0] = shard.device.id
    emit("tier leaf -> device: " + " ".join(
        f"{leaf}:{dev}" for leaf, dev in sorted(leaf_dev.items())))
    if jax.device_count() >= num_leaves:
        check(len(leaf_dev) == num_leaves
              and len(set(leaf_dev.values())) == num_leaves,
              f"tier leaves are not on {num_leaves} distinct devices: "
              f"{leaf_dev}")
    # every slot but the last arrives (a full session would release on
    # its own); then a whole leaf dies with its buffered rows
    rows = [deltas[i % len(deltas)] for i in range(B - 1)]
    stacked = jax.tree.map(lambda *x: jnp.stack(x), *rows)
    n_kern, compile_s = kernels_in(  # the destination-sharded ingest
        tier._ingest_sharded, tier._bufs, tier._wts, tier._norms,
        tier._clips, tier._stal, stacked,
        *tier._route_by_leaf(list(range(B - 1)),
                             np.zeros(B - 1, np.float32)),
        tier._session_key(), jax.random.fold_in(tier._push_base, 0))
    tier.push(stacked, tier.version)
    lost = tier.mark_leaf_dead(dead_leaf)
    check(tier.flush(force=True), "tier: flush refused")
    keep = [s for s in range(B - 1) if s not in lost]

    one = AsyncServer(params, fl, buffer_size=B, mask_mode=mode,
                      staleness_mode="constant", use_pallas=use_pallas)
    for s in keep:
        one.push(rows[s], one.version, slot=s)
    check(one.flush(force=True), "single host: flush refused")
    check(bit_equal(tier.params, one.params),
          "tier release differs from the single host on the survivors")
    for k in ("update_norm", "clip_fraction", "weight_total"):
        check(float(tier.last_metrics[k]) == float(one.last_metrics[k]),
              f"tier metric {k} differs from the single host")
    emit(f"bitcmp tier == single-host AsyncServer (leaf {dead_leaf} dead, "
         f"survivor slots {keep}): True")
    fields = check_reference("flush", tier.params, params,
                             [rows[s] for s in keep], tier._spec.sa_scale)
    if expect_kernels:
        check(n_kern > 0, "tier: no Pallas kernel in the compiled ingest")
    return dict(use_pallas=use_pallas, tpu_custom_call=n_kern, mode=mode,
                leaves=num_leaves, dead_leaf=dead_leaf, **fields,
                compile_s=compile_s, wall_s=time.perf_counter() - t0)


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two-level tier over four chips")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend {backend!r}); this "
              f"script runs only on the chip", file=sys.stderr)
        return 2
    from repro.configs import registry
    from repro.launch.cache import enable_compile_cache

    t_start = time.perf_counter()
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={jax.device_count()}")
    print(f"compile cache: {enable_compile_cache()}")

    def emit(s):
        print(s, flush=True)

    cfg = registry.get_config("whisper-tiny", reduced=False)
    fl = fl_config()
    model, params, batch = make_setup(cfg, args.seed, BUFFER)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    emit(f"setup: model={cfg.name} D={n_params} "
         f"leaves={len(jax.tree.leaves(params))}")
    kw = dict(use_pallas=True, expect_kernels=True)
    if args.four_chips:
        if jax.device_count() != 4:
            raise PhaseFailed(f"--four-chips needs 4 devices, JAX sees "
                              f"{jax.device_count()}")
        deltas = random_deltas(params, fl, 8, args.seed)
        emit(line("tier", **tier_phase(params, deltas, fl, emit=emit, **kw)))
    else:
        deltas, fields = client_phase(model, params, batch, fl)
        emit(line("client", **fields))
        fedbuff_phases(params, deltas, fl, emit=emit, **kw)
        emit(line("sync", **sync_phase(model, params, batch, deltas, fl,
                                       expect_kernels=True)))
        emit(line("sketch", **sketch_phase(params, deltas, fl, **kw)))
    emit(f"total: wall_s={time.perf_counter() - t_start:.2f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
