"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (params and the delta pool from the seed, the engine, a warm-up of
every program the window runs) counts as ``setup_s``; then the cell's
traffic is driven for ``--seconds``; then a sample of the releases, drawn
from the seed, is compared with the plain f32 reference
(``chipbench/reference.py``).  With ``--trace 1`` the window runs under the
profiler and the metrics are the cell's per-layer metrics.  Off a TPU it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    try:
        from chipbench import harness
        cell = harness.resolve(args.workload)
        import jax
        from repro.launch.cache import enable_compile_cache
    except (ImportError, KeyError, FileNotFoundError) as e:
        print(f"chipbench: cannot set up {args.workload}: {e!r}",
              file=sys.stderr)
        return 2
    if jax.default_backend() != "tpu":  # no CPU fallback
        print(f"chipbench: JAX found no TPU (platform "
              f"{jax.default_backend()!r}); the benchmark runs only on the "
              f"chip", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # every program, small ones too, comes from the cache after a first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=T_PROCESS,
                           log=lambda s: print(s, flush=True))
    for name, (value, limit) in out["check"].items():
        print(f"check {name}: {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
