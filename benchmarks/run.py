"""Benchmark harness entry point — one module per paper table/figure plus
the beyond-paper fault/kernel/serving/LM benches. Prints
``name,us_per_call,derived`` CSV rows (and collects them in
benchmarks.common.ROWS).

    PYTHONPATH=src python -m benchmarks.run            # full
    PYTHONPATH=src python -m benchmarks.run --quick    # CI-sized
    PYTHONPATH=src python -m benchmarks.run --smoke    # toy sizes, seconds
    PYTHONPATH=src python -m benchmarks.run --only fig1
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="toy sizes for every suite — exercises the whole "
                         "harness in seconds (CI), including the routed "
                         "serve path and the deadline-flusher p99 "
                         "simulation")
    ap.add_argument("--only", default=None,
                    help="substring filter: fig1|fig2|fig3|table1|fault|"
                         "kernel|serve|lm")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write machine-readable results (rows + headline "
                         "metrics such as amortized speedup and p50/p99 "
                         "serve latency) to PATH, e.g. BENCH_serve.json — "
                         "the cross-PR perf trajectory file")
    args = ap.parse_args()
    quick = args.quick or args.smoke

    from repro.launch import compile_cache
    compile_cache.enable()

    from benchmarks import (bench_complexity, bench_fault, bench_kernels,
                            bench_lm_smoke, bench_serve_latency,
                            bench_vary_data, bench_vary_machines,
                            bench_vary_param)

    # --smoke shrinks the swept axes to single toy points on top of quick=True
    fig1_sizes = (256,) if args.smoke else bench_vary_data.SIZES
    fig2_machines = (2, 4) if args.smoke else bench_vary_machines.MS
    fig3_values = bench_vary_param.PARAMS[:1] if args.smoke \
        else bench_vary_param.PARAMS

    suites = [
        ("fig1", lambda: [bench_vary_data.run("aimpeak", sizes=fig1_sizes,
                                              quick=quick),
                          bench_vary_data.run("sarcos", sizes=fig1_sizes,
                                              quick=quick)]),
        ("fig2", lambda: bench_vary_machines.run("aimpeak",
                                                 machines=fig2_machines,
                                                 quick=quick)),
        ("fig3", lambda: bench_vary_param.run("aimpeak", values=fig3_values,
                                              quick=quick)),
        ("table1", lambda: bench_complexity.run(quick=quick)),
        ("fault", lambda: bench_fault.run(quick=quick)),
        ("kernel", lambda: bench_kernels.run(quick=quick)),
        ("serve", lambda: bench_serve_latency.run(quick=args.quick,
                                                  smoke=args.smoke)),
        ("lm", lambda: bench_lm_smoke.run(quick=quick)),
    ]
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suites:
        if args.only and args.only not in name:
            continue
        try:
            fn()
        except Exception as e:  # keep the harness going, report at exit
            failures.append((name, repr(e)))
            print(f"{name}/ERROR,0,{e!r}")
    if args.json:
        # written even on failure: a partial trajectory beats none, and the
        # exit code still flags the run
        from benchmarks import common
        common.write_json(args.json, argv=sys.argv[1:])
        print(f"wrote {args.json} ({len(common.ROWS)} rows, "
              f"{len(common.METRICS)} metrics)")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
