"""One traced run of a cell through the benchmark's own runner that also
keeps the window's stage timers, and writes what the trace says about the
device's idle time against the program's ``serving.*`` stage spans to a
JSON file.

    python3 benchmarks/chip/stage_probe.py --workload danube2.chat \
        --seed 7 --seconds 51 --out probe.json

The last lines on standard output are the run's result (as ``run.py --trace
1`` prints it) with the window's stage means, then the ten longest device
idle gaps, then the idle shares: with any ``serving.*`` span open, and with
each.  The trace is read by ``chipbench.trace_reduce``, as in every traced
run; a gap is named by the innermost ``serving.*`` span open across its
middle, else by the benchmark's span.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def stage_means(window) -> dict:
    """{stage: {count, mean_ms}} of every program stage timer that counted
    in the window."""
    out = {}
    for name in sorted(set(window.stages0) | set(window.stages1)):
        total, count = window.stage(name)
        if count:
            out[name] = {"count": count, "mean_ms": 1e3 * total / count}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    from chipbench import manifest, runner
    sys.path.insert(0, str(manifest.ROOT / "src"))
    got = {}
    real_read = runner.read_metrics

    def read_metrics(specs, window):
        got["window"] = window
        return real_read(specs, window)

    runner.read_metrics = read_metrics
    result, checks = runner.run(args.workload, args.seed, args.seconds, True,
                                T_START)
    import numpy as np
    w = got["window"]
    lat = w.latencies_ms()
    probe = {"stages": stage_means(w),
             "p50_ms": float(np.percentile(lat, 50)) if lat else None,
             "p95_ms": float(np.percentile(lat, 95)) if lat else None,
             "n": len(lat)}
    shares = {k: w.trace[k] for k in ("idle_share", "idle_in_program_share",
                                      "idle_with_stage_open_share")}
    with open(args.out, "w") as f:
        json.dump({"result": result, "probe": probe, "trace": shares}, f,
                  indent=1)
    for line in checks:
        print(line, file=sys.stderr)
    print(json.dumps({"result": result, "probe": probe}))
    print(json.dumps(w.trace["breakdown"]["idle_gaps"]))
    print(json.dumps(shares), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
