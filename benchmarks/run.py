"""Benchmark harness — one module per paper table/figure/claim.

  bench_label_balance   Paper Fig. 3 (score-distribution skew)
  bench_feature_norm    Paper Fig. 4 (loss reduction / accuracy gain)
  bench_noise_placement Paper §Model aggregation (tee vs device noise)
                        + §Abstract ("minimal degradation" vs central)
  bench_async           Paper §Training (Papaya 5x / 8x claims)
  bench_comm            Secure-agg bytes vs quantization width
  bench_fa_bits         FA bit-protocol estimator error scaling
  bench_kernels         Kernel micro-timings + TPU roofline context
  bench_hierarchy       Aggregation-tier scaling (leaves x buffer x dim,
                        flat vs two-level session tree, dead-leaf flush)
  bench_churn           Churn profile x {FedBuff,FedProx,SCAFFOLD} x mask
                        mode: round success rate, wasted work, steps to
                        target loss (-> results/churn_robustness.csv)
  bench_telemetry       Telemetry recorder overhead on the async critical
                        path (-> results/telemetry_overhead.csv)

Prints ``name,us_per_call,derived`` CSV.  ``--trace PATH`` installs a
span-recording registry as the process default and writes a Chrome
trace-event JSON (load it in Perfetto / chrome://tracing) covering every
benchmark, one top-level span per module.
"""
import argparse
import sys
import traceback

from benchmarks.common import header


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="write a Chrome trace-event JSON of the whole "
                         "benchmark run (spans recorded on the default "
                         "telemetry registry)")
    args = ap.parse_args(argv)

    tel = None
    if args.trace:
        from repro.core import telemetry as tele

        tel = tele.Telemetry(record_spans=True, max_spans=2_000_000)
        tele.set_default(tel)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    header()
    import benchmarks.bench_label_balance as b1
    import benchmarks.bench_feature_norm as b2
    import benchmarks.bench_noise_placement as b3
    import benchmarks.bench_async as b4
    import benchmarks.bench_comm as b5
    import benchmarks.bench_fa_bits as b6
    import benchmarks.bench_kernels as b7
    import benchmarks.bench_hierarchy as b8
    import benchmarks.bench_churn as b9
    import benchmarks.bench_telemetry as b10

    failures = 0
    for mod in (b1, b2, b3, b4, b5, b6, b7, b8, b9, b10):
        try:
            if tel is not None:
                short = mod.__name__.rsplit(".", 1)[-1]
                with tel.span(short):
                    mod.run()
            else:
                mod.run()
        except Exception:
            failures += 1
            print(f"# FAILED {mod.__name__}", file=sys.stderr)
            traceback.print_exc()
    if tel is not None:
        from repro.core.obs import write_chrome_trace

        write_chrome_trace(tel, args.trace)
        print(f"# trace: {args.trace} ({len(tel.spans)} spans)",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
