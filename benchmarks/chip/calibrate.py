"""Readings the benchmark's rate and limits are set from.  Not part of a
benchmark run; run it on the chip by hand.

    # the knee: one set-up, then, for each offered rate in rising order,
    # windows of the cell's schedule at that rate, one per structure seed
    python3 benchmarks/chip/calibrate.py --workload danube2.chat \
        --sweep 10,12,14,16 --windows 3 --seconds 51

    # correctness readings: for each seed, a short window of the cell's own
    # traffic, then the check on the program's answers and on the control's
    python3 benchmarks/chip/calibrate.py --workload danube2.chat \
        --seeds 1,2,3 --seconds 10

The knee is the highest swept rate at which that rate and every lower one
are sustained (:func:`sustained`).  The control is the reference with every
matrix rounded to int8, the precision below the configuration's bfloat16,
put in the program's place and judged by the same check as a run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

BACKLOG_POINTS = 50        # samples of the backlog in each third
ANSWER_WITHIN_S = 5.0      # every request answered this soon after the close


def backlog(window) -> dict:
    """Requests due but not yet answered, averaged over the first and the
    last third of the window; and how many were answered later than
    :data:`ANSWER_WITHIN_S` after the close, or never."""
    recs, t0, s = window.records, window.t0, window.seconds

    def mean_backlog(a, b):
        pts = [t0 + a + (b - a) * k / BACKLOG_POINTS
               for k in range(BACKLOG_POINTS)]
        return sum(sum(1 for r in recs if r.due <= t and
                       (r.done is None or r.done > t))
                   for t in pts) / BACKLOG_POINTS

    late = sum(1 for r in recs if r.done is None or
               r.done > window.close + ANSWER_WITHIN_S)
    return {"backlog_first": mean_backlog(0, s / 3),
            "backlog_last": mean_backlog(2 * s / 3, s),
            "answered_late": late}


def sustained(windows: list, in_flight: int) -> bool:
    """A rate is sustained when, over its windows, the backlog starts below
    the system's in-flight window in each, does not grow (the mean over the
    windows' last thirds is at most 1.5 x that over their first thirds,
    plus one request), and every request is answered in time."""
    first = sum(w["backlog_first"] for w in windows) / len(windows)
    last = sum(w["backlog_last"] for w in windows) / len(windows)
    return (all(w["backlog_first"] < in_flight for w in windows)
            and last <= 1.5 * first + 1.0
            and all(w["answered_late"] == 0 for w in windows))


def knee(verdicts: list):
    """The highest rate of ``[(rate, sustained), ...]`` at which it and every
    lower rate are sustained; ``None`` where the lowest is not."""
    best = None
    for rate, ok in sorted(verdicts):
        if not ok:
            break
        best = rate
    return best


def sweep(served, mix, args, log) -> None:
    from chipbench import harness
    import numpy as np
    in_flight = served.system.max_in_flight
    verdicts = []
    for rate in sorted(float(r) for r in args.sweep.split(",")):
        rows = []
        for k in range(args.windows):
            m = dataclasses.replace(mix.with_rate(rate),
                                    structure_seed=mix.structure_seed + k)
            w = harness.measure(served, m, args.seed, args.seconds, log)
            lat = w.latencies_ms()
            row = {"rate_per_s": rate, "structure_seed": m.structure_seed,
                   "requests": len(w.records),
                   "rows": sum(r.rows for r in w.records),
                   "p50_ms": float(np.percentile(lat, 50)),
                   "p95_ms": float(np.percentile(lat, 95)),
                   "max_lateness_ms": 1e3 * max(r.sent - r.due
                                                for r in w.records),
                   **backlog(w)}
            print("window " + json.dumps(row), flush=True)
            rows.append(row)
        ok = sustained(rows, in_flight)
        verdicts.append((rate, ok))
        print("rate " + json.dumps({"rate_per_s": rate, "sustained": ok}),
              flush=True)
        if not ok:
            break
    print("knee " + json.dumps({"knee_per_s": knee(verdicts),
                                "in_flight": in_flight}), flush=True)


def readings(cfg, ref, mix, chips, args, log) -> None:
    from chipbench import harness, runner
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        served = harness.build(cfg, ref, mix.seq, seed, chips)
        harness.warm_up(served, mix, seed)
        w = harness.measure(served, mix, seed, args.seconds, log)
        harness.release(served)
        for run in ("program", "control_int8"):
            if run == "control_int8":
                runner.put_control(w, ref, cfg, seed, chips)
            ok, checks = runner.check(w, ref, cfg, seed, chips)
            print("reading " + json.dumps(
                {"seed": seed, "run": run, "correct": ok,
                 "requests": len(w.records),
                 **{k: c["value"] for k, c in checks.items()},
                 "limits": {k: c["limit"] for k, c in checks.items()},
                 "seconds": time.perf_counter() - t0}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--sweep", default="",
                    help="offered rates, req/s, comma-separated")
    ap.add_argument("--windows", type=int, default=3,
                    help="windows per swept rate, one per structure seed")
    ap.add_argument("--seeds", default="",
                    help="seeds of the correctness readings")
    ap.add_argument("--seed", type=int, default=1,
                    help="weights and tokens of the sweep")
    args = ap.parse_args(argv)
    from chipbench import harness, manifest, runner
    sys.path.insert(0, str(manifest.ROOT / "src"))
    _man, cell, cfg, mix, ref = runner.load_cell(args.workload)
    runner.configure_jax(manifest.cache_dir())
    log = harness.CompileLog()
    chips = harness.chips_for(int(cell["chips"]))
    if args.sweep:
        served = harness.build(cfg, ref, mix.seq, args.seed, chips)
        harness.warm_up(served, mix, args.seed)
        sweep(served, mix, args, log)
        harness.release(served)
    if args.seeds:
        readings(cfg, ref, mix, chips, args, log)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
