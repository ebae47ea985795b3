"""FL training driver: real execution (CPU-scale) of the full system.

Runs the complete paper pipeline on a synthetic device population:
  orchestrator cohort selection -> federated analytics (label ratio,
  normalization) -> DP-FL rounds with secure aggregation -> DP metric
  calculation -> checkpointing -> RDP privacy accounting.

Usage (reduced LLM arch):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --rounds 20 --cohort 16 --seq-len 64
  PYTHONPATH=src python -m repro.launch.train --classifier --rounds 100
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--classifier", action="store_true",
                    help="paper-faithful MLP binary classifier workload")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--cohort", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--local-steps", type=int, default=1)
    ap.add_argument("--local-lr", type=float, default=0.5)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--noise", type=float, default=0.3)
    ap.add_argument("--noise-placement", default="tee", choices=["tee", "device"])
    ap.add_argument("--server-opt", default="fedavg")
    ap.add_argument("--server-lr", type=float, default=1.0)
    ap.add_argument("--population", type=int, default=4096)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)

    from repro.configs.base import FLConfig
    from repro.core.fl.accountant import RDPAccountant
    from repro.core.fl.round import build_round_step, init_fl_state
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    fl_cfg = FLConfig(
        cohort_size=args.cohort, local_steps=args.local_steps,
        local_lr=args.local_lr, clip_norm=args.clip,
        noise_multiplier=args.noise, noise_placement=args.noise_placement,
        server_opt=args.server_opt, server_lr=args.server_lr,
    )
    key = jax.random.PRNGKey(args.seed)

    if args.classifier:
        model, make_batch = _classifier_workload(args, key)
    else:
        model, make_batch = _llm_workload(args, key)

    params = model.init(key)
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    print(f"model params: {n_params:,}")

    state = init_fl_state(params, fl_cfg)
    round_step = jax.jit(build_round_step(
        model.loss_fn, fl_cfg, cohort_size=args.cohort,
        clients_per_chunk=min(args.cohort, 8)))
    accountant = RDPAccountant()
    q = args.cohort / args.population

    t0 = time.time()
    for r in range(args.rounds):
        rng = jax.random.fold_in(key, 10_000 + r)
        batch = make_batch(r)
        state, metrics = round_step(state, batch, rng)
        accountant.step(q, args.noise)
        if r % args.log_every == 0 or r == args.rounds - 1:
            eps = accountant.epsilon(1e-6) if args.noise > 0 else float("inf")
            print(f"round {r:4d} loss={float(metrics['loss']):.4f} "
                  f"clip%={float(metrics['clip_fraction']):.2f} "
                  f"|u|={float(metrics['update_norm']):.3f} "
                  f"eps(1e-6)={eps:.2f} ({time.time() - t0:.1f}s)")
        if args.checkpoint_dir and (r + 1) % args.checkpoint_every == 0:
            from repro.checkpoint.checkpoint import save
            path = os.path.join(args.checkpoint_dir, f"step_{r + 1}")
            save(path, {"params": state.params, "opt": state.opt_state},
                 step=r + 1, metadata={"arch": args.arch, "fl": vars(args)})
            print(f"  checkpointed -> {path}")
    print(f"done in {time.time() - t0:.1f}s")
    return 0


def _classifier_workload(args, key):
    from repro.configs import mlp as mlp_cfg
    from repro.data.synthetic import ClassifierTask
    from repro.models.model import build_mlp_classifier

    cfg = mlp_cfg.CONFIG
    task = ClassifierTask(num_features=cfg.num_features, seed=args.seed)
    mean, std = task.normalization_oracle()
    model = build_mlp_classifier(cfg)

    def make_batch(r):
        data = task.sample_devices(args.cohort, rng_seed=args.seed * 977 + r)
        x = (data["features_raw"] - mean) / np.maximum(std, 1e-6)
        return {"features": jnp.asarray(x)[:, None, :],
                "label": jnp.asarray(data["label"])[:, None]}

    return model, make_batch


def _llm_workload(args, key):
    from repro.configs import registry
    from repro.data.synthetic import fl_token_batch
    from repro.models.model import build_model

    cfg = registry.get_config(args.arch, reduced=args.reduced)
    cfg = cfg.with_overrides(max_seq_len=max(args.seq_len, 64))
    model = build_model(cfg)

    def make_batch(r):
        b = fl_token_batch(args.cohort, args.seq_len, cfg.vocab_size,
                           seed=args.seed * 7919 + r)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.family == "vlm":
            batch["patch_embeds"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, r),
                (args.cohort, 1, cfg.num_image_tokens, cfg.d_model))
        if cfg.family == "audio":
            batch["audio_embeds"] = 0.02 * jax.random.normal(
                jax.random.fold_in(key, r),
                (args.cohort, 1, cfg.encoder_seq, cfg.d_model))
        return batch

    return model, make_batch


if __name__ == "__main__":
    raise SystemExit(main())
