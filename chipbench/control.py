"""The control of the correctness check: the reference in a lower precision.

For a cell and some seeds, builds the cell's params and delta pool at its own
size, takes the first sessions its traffic would release, and compares the
reference computed with the contributions in bfloat16 (the step below the
configuration's float32 that would tempt a later change: a bf16 buffer or
wire) against the float32 reference, by the same ``err_over_bound`` as a
run.  Every reading has to come out far above the cell's limit.

    python3 chipbench/control.py --workload <name> --seeds 11 12 13
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent


def session_weights(mix: dict, buffer: int, seed: int, r: int):
    """(weights per pool entry, contributions) of session ``r`` of a mix,
    as the drivers compose it."""
    import numpy as np
    from chipbench import drive, traffic
    size = int(mix["pool"]["size"])
    w = np.zeros(size)
    if mix["kind"] == "backlog":
        stal = traffic.staleness(mix, seed, (r + 1) * buffer)
        for i in range(r * buffer, (r + 1) * buffer):
            w[i % size] += drive.staleness_weight(stal[i])
        return w, buffer
    absent = set(traffic.absent_slots(mix, seed, buffer, r + 1)[r])
    for s in range(buffer):
        if s not in absent:
            w[s % size] += 1.0
    return w, buffer - len(absent)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--sessions", type=int, default=2)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import jax
    import jax.numpy as jnp
    from chipbench import drive, harness, reference
    cell = harness.resolve(args.workload)
    fl = cell.config["fl"]
    buffer = harness.contributors(cell.config)
    readings = []
    for seed in args.seeds:
        params = drive.make_params(cell.config, seed)
        entries = drive.pool_entries(
            drive.make_pool(params, cell.mix, seed),
            drive.stack_rows(cell.mix))
        for r in range(args.sessions):
            w, n = session_weights(cell.mix, buffer, seed, r)
            rng = jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)), r)
            x = reference.compare(
                None, params, entries, w, n, float(w.sum()),
                clip_norm=fl["clip_norm"],
                noise_multiplier=fl["noise_multiplier"], rng=rng,
                field_bits=fl["secure_agg_bits"], contributors=buffer,
                value_range=fl.get("secure_agg_range", 4.0),
                dtype=jnp.bfloat16)
            readings.append(x)
            print(f"control {args.workload} seed {seed} session {r}: "
                  f"err_over_bound {x!r} (limit "
                  f"{cell.config['check']['err_over_bound']!r})", flush=True)
        del params, entries
    print(json.dumps({"workload": args.workload, "control_min":
                      min(readings), "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
