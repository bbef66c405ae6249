"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table1,table3,...]
                                            [--json BENCH_serving.json]

Emits CSV lines ``<table>:<fields...>`` so results can be grepped/diffed, and
writes a machine-readable ``BENCH_serving.json`` with the serving results
(segments/sec, per-stage timings, overhead) for CI trend tracking.
"""
import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: overhead,serving,sim,table1,table3,"
                         "stability,roofline")
    ap.add_argument("--json", default="BENCH_serving.json",
                    help="path for the machine-readable serving results "
                         "('' disables)")
    ap.add_argument("--seed", type=int, default=0,
                    help="workload RNG seed for the serving job (recorded "
                         "in the JSON as serving.rng_seed)")
    ap.add_argument("--scenario", action="append", default=None,
                    help="serving scenario to run (repeatable); default all "
                         "— see benchmarks/serving_hotpath.py SCENARIOS")
    args = ap.parse_args()
    want = set(filter(None, args.only.split(",")))

    from repro.compile_cache import enable_compile_cache
    print(f"# compile cache: {enable_compile_cache()}", flush=True)

    from benchmarks import (overhead, roofline_report, serving_hotpath,
                            sim_bench, stability, table1_throughput,
                            table3_bbs)
    jobs = [
        ("overhead", overhead.run),          # paper §IV.A
        ("serving",                          # hot-path A/B (ISSUE 1)
         lambda: serving_hotpath.run(seed=args.seed,
                                     scenarios=args.scenario)),
        ("sim", sim_bench.run),              # discrete-event sim (ISSUE 8)
        ("table1", table1_throughput.run),   # paper Table I
        ("table3", table3_bbs.run),          # paper Table III
        ("stability", stability.run),        # paper §IV.B
        ("roofline", roofline_report.run),   # deliverable (g)
    ]
    serving_results = {}
    for name, fn in jobs:
        if want and name not in want:
            continue
        t0 = time.perf_counter()
        print(f"# --- {name} ---", flush=True)
        try:
            result = fn()
        except Exception as e:  # keep the harness going; report the failure
            print(f"{name}:ERROR,{type(e).__name__}: {e}", file=sys.stderr)
            raise
        if name in ("overhead", "serving", "sim") and isinstance(result, dict):
            serving_results[name] = result
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)

    if args.json and serving_results:
        with open(args.json, "w") as f:
            json.dump(serving_results, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}", flush=True)


if __name__ == "__main__":
    main()
