"""Lower/compile builders for every (architecture x input-shape x mesh).

No jax device-state side effects at import — callers (dryrun.py, tests,
benchmarks) provide the mesh.  Each builder returns the lowered/compiled
artifacts plus the roofline analysis dict.

Train shapes lower the full DP-FL round step (the paper's technique);
prefill shapes lower ``prefill``; decode shapes lower ``serve_step`` — one
new token against a seq_len-deep cache.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.configs.base import FLConfig, ModelConfig, ShapeConfig
from repro.core.fl.round import build_round_step, init_fl_state
from repro.launch import analysis
from repro.launch.mesh import batch_axes
from repro.launch.sharding import (
    cache_specs, infer_batch_specs, param_shardings, to_shardings,
    train_batch_specs,
)
from repro.models.model import build_model

DRYRUN_DTYPE = "bfloat16"


def default_fl_config(cohort: int) -> FLConfig:
    """Paper-faithful round: clip + secure agg (int32 fixed point) + TEE noise."""
    return FLConfig(cohort_size=cohort, local_steps=1, local_lr=1.0,
                    clip_norm=1.0, noise_multiplier=1.0, noise_placement="tee",
                    secure_agg_bits=32, server_opt="fedavg", server_lr=1.0)


def _prep_cfg(cfg: ModelConfig, opts: Dict) -> ModelConfig:
    over = {"param_dtype": opts.get("dtype", DRYRUN_DTYPE),
            "compute_dtype": opts.get("dtype", DRYRUN_DTYPE)}
    for k in ("remat", "attn_seq_shard", "attention_window", "attn_q_chunk",
              "capacity_factor", "moe_dispatch"):
        if k in opts:
            over[k] = opts[k]
    return cfg.with_overrides(**over)


def _model_flops(cfg: ModelConfig, shape: ShapeConfig, fl_cfg: Optional[FLConfig]) -> float:
    n = cfg.active_param_count()
    if shape.mode == "train":
        tokens = shape.global_batch * shape.seq_len * (fl_cfg.local_steps if fl_cfg else 1)
        return 6.0 * n * tokens
    if shape.mode == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


# ---------------------------------------------------------------------------
def build_train(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                fl_cfg: Optional[FLConfig] = None, opts: Optional[Dict] = None):
    """Returns (jitted_fn, example_args_sds) for the DP-FL round step."""
    opts = opts or {}
    cfg = _prep_cfg(cfg, opts)
    fl_cfg = fl_cfg or default_fl_config(shape.global_batch)
    if "deferred_agg" in opts or "noise_placement" in opts or "local_steps" in opts:
        fl_cfg = FLConfig(**{**fl_cfg.__dict__,
                             **{k: opts[k] for k in
                                ("deferred_agg", "noise_placement", "local_steps")
                                if k in opts}})
    model = build_model(cfg, use_ragged_moe=opts.get("use_ragged_moe", False))

    cohort = shape.global_batch
    ba = batch_axes(mesh)
    n_batch_shards = int(np.prod([mesh.shape[a] for a in ba]))
    client_parallel = opts.get("client_parallel", not cfg.fsdp)
    if client_parallel:
        m = n_batch_shards
        client_axis, seq_axis = ba, None
    else:
        # sequential clients; each client's sequence shards over `data` and
        # (multi-pod) a small client chunk shards over `pod`.
        m = mesh.shape.get("pod", 1)
        client_axis = ("pod",) if "pod" in mesh.shape else None
        seq_axis = "data"
    m = opts.get("clients_per_chunk", m)

    round_step = build_round_step(model.loss_fn, fl_cfg, cohort_size=cohort,
                                  client_parallel=client_parallel,
                                  clients_per_chunk=m)

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    state_sds = jax.eval_shape(lambda p: init_fl_state(p, fl_cfg), params_sds)
    fsdp_axis = "data" if (cfg.fsdp and not client_parallel) else None
    state_sh = param_shardings(state_sds, mesh, tp="model", fsdp_axis=fsdp_axis)

    raw = registry.input_specs(cfg, shape)
    batch_sds = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((s.shape[0], 1) + s.shape[1:], s.dtype), raw)
    batch_specs = train_batch_specs(batch_sds, mesh, client_axis=client_axis,
                                    seq_axis=seq_axis)
    batch_sh = to_shardings(batch_specs, mesh)

    rng_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    rng_sh = NamedSharding(mesh, P())

    fn = jax.jit(round_step, in_shardings=(state_sh, batch_sh, rng_sh),
                 out_shardings=(state_sh, None))
    return fn, (state_sds, batch_sds, rng_sds), {"fl_cfg": fl_cfg, "m": m,
                                                 "client_parallel": client_parallel}


def build_prefill(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                  opts: Optional[Dict] = None):
    opts = opts or {}
    cfg = _prep_cfg(cfg, opts)
    model = build_model(cfg, use_ragged_moe=opts.get("use_ragged_moe", False))
    max_len = shape.seq_len

    def prefill_fn(params, batch):
        return model.prefill(params, batch, max_len)

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = param_shardings(params_sds, mesh, tp="model", fsdp_axis=None)
    batch_sds = registry.input_specs(cfg, shape)
    batch_sh = to_shardings(infer_batch_specs(batch_sds, mesh), mesh)
    cache_sds = jax.eval_shape(prefill_fn, params_sds, batch_sds)[1]
    cache_sh = to_shardings(
        cache_specs(cache_sds, mesh, shard_seq=opts.get("shard_seq", False)), mesh)

    fn = jax.jit(prefill_fn, in_shardings=(params_sh, batch_sh),
                 out_shardings=(None, cache_sh))
    return fn, (params_sds, batch_sds), {}


def build_decode(cfg: ModelConfig, shape: ShapeConfig, mesh, *,
                 opts: Optional[Dict] = None):
    opts = opts or {}
    cfg = _prep_cfg(cfg, opts)
    model = build_model(cfg, use_ragged_moe=opts.get("use_ragged_moe", False))
    B = shape.global_batch
    max_len = shape.seq_len

    params_sds = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params_sh = param_shardings(params_sds, mesh, tp="model", fsdp_axis=None)
    cache_sds = jax.eval_shape(lambda: model.init_cache(B, max_len))
    shard_seq = opts.get("shard_seq", False)
    cache_sh = to_shardings(cache_specs(cache_sds, mesh, shard_seq=shard_seq), mesh)
    tok_sds = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = to_shardings(infer_batch_specs(tok_sds, mesh), mesh)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    pos_sh = NamedSharding(mesh, P())

    def decode_fn(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos)

    donate = (1,) if opts.get("donate_cache", False) else ()
    fn = jax.jit(decode_fn,
                 in_shardings=(params_sh, cache_sh, tok_sh, pos_sh),
                 out_shardings=(None, cache_sh),
                 donate_argnums=donate)
    return fn, (params_sds, cache_sds, tok_sds, pos_sds), {}


# ---------------------------------------------------------------------------
# Cost probes.
#
# XLA's HloCostAnalysis counts while-loop bodies ONCE (verified empirically:
# an 8-trip scan of a matmul reports 1 matmul of flops).  The deployable
# artifact keeps its loops (memory_analysis + fits-proof come from it); the
# roofline cost terms come from a PROBE lowering with every scan unrolled —
# and, for train, a single client-chunk whose costs are multiplied by
# n_chunks (the chunk loop is data-identical across trips).
# ---------------------------------------------------------------------------
def _probe_overrides(cfg: ModelConfig, shape: ShapeConfig) -> ModelConfig:
    return cfg.with_overrides(scan_unroll=True, attn_q_chunk=shape.seq_len,
                              remat=False)


def _probe_train(cfg, shape, mesh, fl_cfg, opts, meta):
    """Single-client chunk probe for per-device flops/bytes.

    client_parallel mode: the real per-device program computes ONE client's
    grad (clients shard the data axis) per chunk, so we probe one client on a
    TP-only submesh (data=1) — identical per-device cost, tiny compile.
    sequential mode: the real chunk already is one client on the full mesh.
    The per-device multiplier is the number of chunks each device works
    through: cohort / m.
    """
    import jax as _jax
    probe_cfg = _probe_overrides(cfg, shape)
    probe_shape = ShapeConfig(shape.name, shape.seq_len,
                              1 if meta["client_parallel"] else meta["m"],
                              "train")
    probe_fl = FLConfig(**{**fl_cfg.__dict__,
                           "cohort_size": probe_shape.global_batch})
    popts = dict(opts)
    popts["clients_per_chunk"] = probe_shape.global_batch
    if meta["client_parallel"]:
        tp = mesh.shape["model"]
        from repro.launch.mesh import make_mesh
        probe_mesh = make_mesh((1, tp), ("data", "model"))
    else:
        probe_mesh = mesh
    fn, args, _ = build_train(probe_cfg, probe_shape, probe_mesh,
                              fl_cfg=probe_fl, opts=popts)
    n_chunks = shape.global_batch // meta["m"]
    return fn, args, float(n_chunks), probe_mesh


def _probe_serve(cfg, shape, mesh, opts, build):
    probe_cfg = _probe_overrides(cfg, shape)
    fn, args, _ = build(probe_cfg, shape, mesh, opts=opts)
    return fn, args, 1.0


def lower_pair(arch: str, shape_name: str, mesh, *, reduced: bool = False,
               opts: Optional[Dict] = None, compile_: bool = True,
               cost_probe: bool = True) -> Dict[str, Any]:
    """Lower (+compile) one (arch, shape) on the given mesh; return analysis."""
    opts = dict(opts or {})
    cfg = registry.config_for_pair(arch, shape_name, reduced=reduced)
    if cfg is None:
        return {"arch": arch, "shape": shape_name,
                "skipped": registry.SKIPS[(arch, shape_name)]}
    shape = registry.get_shape(shape_name)
    if reduced:
        shape = ShapeConfig(shape.name, min(shape.seq_len, 256),
                            min(shape.global_batch, 8), shape.mode)

    fl_cfg = None
    if shape.mode == "train":
        fl_cfg = opts.pop("fl_cfg", None) or default_fl_config(shape.global_batch)
        fn, args, meta = build_train(cfg, shape, mesh, fl_cfg=fl_cfg, opts=opts)
    elif shape.mode == "prefill":
        fn, args, meta = build_prefill(cfg, shape, mesh, opts=opts)
    else:
        fn, args, meta = build_decode(cfg, shape, mesh, opts=opts)

    with mesh:
        lowered = fn.lower(*args)
        out: Dict[str, Any] = {
            "arch": arch, "shape": shape_name, "mesh": dict(mesh.shape),
            "reduced": reduced, "mode": shape.mode, **meta,
        }
        out.pop("fl_cfg", None)
        if compile_:
            compiled = lowered.compile()
            chips = int(np.prod(list(mesh.shape.values())))
            out["memory"] = analysis.memory_summary(compiled)

            # cost terms from the unrolled probe
            if cost_probe and shape.mode == "train":
                pfn, pargs, mult, pmesh = _probe_train(cfg, shape, mesh,
                                                       fl_cfg, opts, meta)
                with pmesh:
                    pcompiled = pfn.lower(*pargs).compile()
                out["roofline"] = analysis.roofline(
                    pcompiled, pcompiled.as_text(),
                    model_flops=_model_flops(cfg, shape, fl_cfg),
                    chips=chips, multiplier=mult)
                if meta["client_parallel"]:
                    # probe submesh (data=1) misses the cross-data aggregation
                    # collectives; take those from the looped full compile —
                    # in-loop (while-body) collectives x n_chunks, entry-level
                    # ones (e.g. the deferred post-scan reduction) x 1.
                    full_coll = analysis.collective_summary(
                        compiled.as_text(), loop_multiplier=mult)
                    probe_coll = out["roofline"]["collectives"]
                    wire = full_coll["total_wire_bytes"]
                    out["roofline"]["collectives"] = {
                        "ops": full_coll["ops"],
                        "total_bytes": full_coll["total_bytes"],
                        "total_wire_bytes": wire,
                        "count": full_coll["count"],
                        "probe_tp_only": probe_coll,
                    }
                    out["roofline"]["t_collective_s"] = wire / analysis.peaks(
                        analysis.TARGET_KIND)["ici_link_bytes_per_s"]
                    terms = {"compute": out["roofline"]["t_compute_s"],
                             "memory": out["roofline"]["t_memory_s"],
                             "collective": out["roofline"]["t_collective_s"]}
                    out["roofline"]["dominant"] = max(terms, key=terms.get)
                    out["roofline"]["bound_time_s"] = max(terms.values())
                out["roofline"]["cost_probe_multiplier"] = mult
            elif cost_probe:
                build = build_prefill if shape.mode == "prefill" else build_decode
                pfn, pargs, mult = _probe_serve(cfg, shape, mesh, opts, build)
                pcompiled = pfn.lower(*pargs).compile()
                out["roofline"] = analysis.roofline(
                    pcompiled, pcompiled.as_text(),
                    model_flops=_model_flops(cfg, shape, fl_cfg),
                    chips=chips, multiplier=mult)
                out["roofline"]["cost_probe_multiplier"] = mult
            else:
                out["roofline"] = analysis.roofline(
                    compiled, compiled.as_text(),
                    model_flops=_model_flops(cfg, shape, fl_cfg), chips=chips)
    return out
