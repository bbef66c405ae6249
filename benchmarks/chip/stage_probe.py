"""One traced run of a cell through the benchmark's own runner that also
keeps the trace's ``serving.*`` host spans (the program's stages) and the
window's stage timers, and writes what they say about the device's idle
time to a JSON file.

    python3 benchmarks/chip/stage_probe.py --workload danube2.chat \
        --seed 7 --seconds 51 --out probe.json [--checkout DIR]

``--checkout`` runs the program and the benchmark of another checkout (a
parent commit unpacked beside this one) under this file's analysis.  The
last lines on standard output are the run's result (as ``run.py --trace
1`` prints it) with the window's stage means, then the ten longest device
idle gaps, then the idle shares.  A gap is named by the innermost
``serving.*`` span open across its middle, else by the benchmark's span
as ``trace_reduce.reduce`` names it.  A program without ``serving.*``
spans reads an idle-in-program share of 0.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

PROGRAM_PREFIX = "serving."


def program_spans(path: str) -> list:
    """``serving.*`` host events of an ``.xplane.pb``: [name, start_ns,
    dur_ns]."""
    from jax.profiler import ProfileData
    return [[e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PROGRAM_PREFIX)]


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _intersect(xs, ys) -> list:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def analyse(trace: dict, program: list, top: int = 10) -> dict:
    """Device idle time inside the traced window against the program's
    spans: the share of the window, averaged over chips, in which a chip was
    idle while any ``serving.*`` span was open (and while each one was), and
    the ``top`` longest idle gaps, each named as the module docstring says,
    with every program span open across its middle."""
    from chipbench import trace_reduce as tr
    lo, hi = tr.window_of(trace)
    window = hi - lo

    def clip(events):
        return tr.union(tr._clip(events, lo, hi))

    names = sorted({n for n, _s, _d in program})
    any_open = clip(program)
    each_open = {n: clip([e for e in program if e[0] == n]) for n in names}
    idle_open, by_stage, gaps = 0.0, dict.fromkeys(names, 0.0), []
    for _dev, events in sorted(trace["devices"].items()):
        idle, prev = [], lo
        for a, b in clip(events) + [(hi, hi)]:
            if a > prev:
                idle.append((prev, a))
                gaps.append((a - prev, prev, a))
            prev = max(prev, b)
        idle_open += _length(_intersect(idle, any_open))
        for n in names:
            by_stage[n] += _length(_intersect(idle, each_open[n]))
    chips = max(len(trace["devices"]), 1)
    bench = [(s, s + d, n) for n, s, d in trace["host"]
             if n != tr.WINDOW_SPAN]
    spans = [(s, s + d, n) for n, s, d in program]
    labelled = []
    for dur, a, b in sorted(gaps, reverse=True)[:top]:
        mid = (a + b) / 2
        inner = [(e - s, n) for s, e, n in spans if s <= mid <= e]
        outer = [(e - s, n) for s, e, n in bench if s <= mid <= e]
        if inner:
            label = min(inner)[1]
        elif outer:
            label = min(outer)[1][len(tr.SPAN_PREFIX):]
        else:
            label = "no span"
        labelled.append([label, dur * 1e-9, sorted({n for _i, n in inner})])
    return {"window_s": window * 1e-9,
            "idle_in_program_share": idle_open / chips / window,
            "idle_with_stage_open_share": {n: v / chips / window
                                           for n, v in by_stage.items()},
            "span_counts": {n: sum(1 for e in program if e[0] == n)
                            for n in names},
            "idle_gaps": labelled}


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
    ap.add_argument("--checkout", default=os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    sys.path.insert(0, os.path.join(args.checkout, "benchmarks", "chip"))
    sys.path.insert(0, os.path.join(args.checkout, "src"))
    from chipbench import runner, trace_reduce
    got = {}
    real_load, real_read = trace_reduce.load, runner.read_metrics

    def load(path):
        got["trace"] = real_load(path)
        got["program"] = program_spans(path)
        return got["trace"]

    def read_metrics(specs, window):
        got["window"] = window
        return real_read(specs, window)

    trace_reduce.load, runner.read_metrics = load, read_metrics
    result, checks = runner.run(args.workload, args.seed, args.seconds, True,
                                T_START)
    import numpy as np
    w = got["window"]
    lat = w.latencies_ms()
    probe = {"stages": stage_means(w),
             "p50_ms": float(np.percentile(lat, 50)) if lat else None,
             "p95_ms": float(np.percentile(lat, 95)) if lat else None,
             "n": len(lat)}
    program = analyse(got["trace"], got["program"])
    with open(args.out, "w") as f:
        json.dump({"result": result, "probe": probe, "program": program}, f,
                  indent=1)
    for line in checks:
        print(line, file=sys.stderr)
    print(json.dumps({"result": result, "probe": probe}))
    print(json.dumps(program["idle_gaps"]))
    print(json.dumps({k: program[k] for k in ("idle_in_program_share",
                                              "idle_with_stage_open_share",
                                              "span_counts")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
