"""Records the small chip trace the reduction's tests read.

    python3 chipbench/testdata/record.py --out <dir>

Two small jitted programs run three times under the benchmark's tracer,
between host spans of known length: ``push`` dispatches them, ``flush``
sleeps 3 ms with the device idle, and 5 ms pass outside any span.  Writes
``tiny.xplane.pb`` and ``tiny.json`` (the tracer's clock marks, the window
and the telemetry spans) into ``--out``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    import jax
    import jax.numpy as jnp
    from chipbench.trace import Tracer
    from repro.core import telemetry as tele

    if jax.default_backend() != "tpu":
        print("record.py: no TPU", file=sys.stderr)
        return 2
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    g = jax.jit(lambda x: (x * 3 + 1).astype(jnp.int32))
    x = jnp.ones((512, 512))
    jax.block_until_ready((f(x), g(x)))
    tel = tele.Telemetry(record_spans=True)
    tracer = Tracer(jax.devices()[:1])
    tracer.start()
    t0 = time.perf_counter_ns()
    for _ in range(3):
        with tel.span("push"):
            y, z = f(x), g(x)
        jax.block_until_ready((y, z))
        time.sleep(0.005)
        with tel.span("flush"):
            time.sleep(0.003)
    t1 = time.perf_counter_ns()
    os.makedirs(args.out, exist_ok=True)
    summary = tracer.stop(window=(t0, t1), tel=tel,
                          keep=os.path.join(args.out, "tiny.xplane.pb"))
    meta = {"marks": tracer.marks, "window": [t0, t1],
            "span_epoch_ns": tel.epoch_ns,
            "spans": [[s.name, s.sid, s.parent, s.t0_ns, s.dur_ns]
                      for s in tel.spans]}
    Path(args.out, "tiny.json").write_text(json.dumps(meta))
    print(json.dumps(summary.breakdown()))
    print(json.dumps({"ok": True, "spans": len(tel.spans)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
