"""Profiler trace -> device busy time, idle share, kernel time, breakdown.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain dict, which is also the form the tests record:

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "programs": {"/device:TPU:0": [[name, start_ns, dur_ns], ...], ...},
     "host": [[name, start_ns, dur_ns], ...],
     "program": [[name, start_ns, dur_ns], ...]}

``devices`` holds each chip's ``XLA Ops`` line -- every operation that ran
on it, named by its HLO instruction; ``programs`` its ``XLA Modules`` line
-- each run of a compiled program, named ``jit_<function>(<fingerprint>)``.
``host`` holds the benchmark's own ``TraceAnnotation`` spans (names
starting ``chipbench.``), ``program`` the program's own stage spans (names
starting ``serving.``), both on the same clock.  :func:`reduce` does the
rest.

A kernel is named in a configuration's ``kernels`` by a regular expression.
By default it matches program names, and the kernel's time is that of the
whole program that runs it, which also moves its operands to and from HBM.
A pattern that starts ``op:`` matches operation names on the ``XLA Ops``
line instead -- a kernel that runs inside a larger program, such as the
member step -- and its time is the operation's own.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

SPAN_PREFIX = "chipbench."
PROGRAM_PREFIX = "serving."
OP_PREFIX = "op:"
WINDOW_SPAN = "chipbench.traced"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
# ops that only contain other ops: their time is their body's
CONTAINERS = ("while", "conditional", "call")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    programs: Dict[str, list] = {}
    host: List[list] = []
    program: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            for line in plane.lines:
                if line.name in (OPS_LINE, PROGRAMS_LINE):
                    out = devices if line.name == OPS_LINE else programs
                    out[plane.name] = [
                        [_op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)] for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out = host if e.name.startswith(SPAN_PREFIX) else \
                        program if e.name.startswith(PROGRAM_PREFIX) else None
                    if out is not None:
                        out.append([e.name, float(e.start_ns),
                                    float(e.duration_ns)])
    return {"devices": devices, "programs": programs, "host": host,
            "program": program}


def _op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _clip(events, lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for _name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    out.sort()
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[list] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _intersect(xs, ys) -> List[Tuple[float, float]]:
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


def _length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def window_of(trace: dict) -> Tuple[float, float]:
    """The traced window: the benchmark's ``chipbench.traced`` span, else the
    extent of all device events."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if spans:
        return spans[0]
    ev = [(s, s + d) for evs in trace["devices"].values() for _n, s, d in evs]
    if not ev:
        raise ValueError("trace holds no device operation")
    return min(a for a, _ in ev), max(b for _, b in ev)


def _is_container(name: str) -> bool:
    """Ops that only contain other ops: ``while.3`` and the like."""
    return re.sub(r"[.\-_]\d+$", "", name) in CONTAINERS


def _kernel_time(trace: dict, dev: str, pattern: str, lo: float,
                 hi: float) -> Tuple[float, int]:
    """Device ns and calls of one ``kernels`` pattern on one device: the
    programs, or with ``op:`` the operations (containers aside), that match
    it and lie wholly inside the window."""
    op = pattern.startswith(OP_PREFIX)
    pat = re.compile(pattern[len(OP_PREFIX):] if op else pattern)
    events = trace["devices"] if op else trace.get("programs", {})
    hits = [d for name, s, d in events.get(dev, [])
            if lo <= s and s + d <= hi and pat.search(name)
            and not (op and _is_container(name))]
    return sum(hits), len(hits)


def reduce(trace: dict, kernels: Optional[Dict[str, str]] = None,
           top: int = 10) -> dict:
    """Busy and idle time per device inside the traced window; the share of
    it in which a chip was idle while one of the program's ``serving.*``
    spans was open, in all and by span; kernel time and calls (see the
    module docstring); and the breakdown: the device ops that took most
    time and the longest idle gaps, each gap named by the innermost
    ``serving.*`` span open across its middle, else by the benchmark span
    open there."""
    lo, hi = window_of(trace)
    window_ns = hi - lo
    busy, gaps, by_op = {}, [], {}
    kernel_ns = {k: 0.0 for k in (kernels or {})}
    kernel_calls = {k: 0 for k in (kernels or {})}
    program = trace.get("program", [])
    stages = sorted({n for n, _s, _d in program})
    any_open = union(_clip(program, lo, hi))
    each_open = {n: union(_clip([e for e in program if e[0] == n], lo, hi))
                 for n in stages}
    idle_open, by_stage = 0.0, dict.fromkeys(stages, 0.0)
    for dev, events in sorted(trace["devices"].items()):
        merged = union(_clip(events, lo, hi))
        busy[dev] = _length(merged)
        idle, prev = [], lo
        for a, b in merged + [(hi, hi)]:
            if a > prev:
                idle.append((prev, a))
                gaps.append((a - prev, prev, a, dev))
            prev = max(prev, b)
        idle_open += _length(_intersect(idle, any_open))
        for n in stages:
            by_stage[n] += _length(_intersect(idle, each_open[n]))
        for name, s, d in events:
            a, b = max(s, lo), min(s + d, hi)
            if b > a and not _is_container(name):
                by_op[name] = by_op.get(name, 0.0) + (b - a)
        for k, pattern in (kernels or {}).items():
            ns, calls = _kernel_time(trace, dev, pattern, lo, hi)
            kernel_ns[k] += ns
            kernel_calls[k] += calls
    n = max(len(busy), 1)
    inside = [(a, b) for evs in trace["devices"].values()
              for a, b in _clip(evs, lo, hi)]
    bench = [(s, s + d, name[len(SPAN_PREFIX):])
             for name, s, d in trace["host"] if name != WINDOW_SPAN]
    spans = [(s, s + d, name) for name, s, d in program]
    gaps.sort(reverse=True)
    idle_gaps = []
    for dur, a, b, _dev in gaps[:top]:
        mid = (a + b) / 2
        open_ = [(e - s, name) for s, e, name in spans if s <= mid <= e] or \
            [(e - s, name) for s, e, name in bench if s <= mid <= e]
        idle_gaps.append([min(open_)[1] if open_ else "no span", dur * 1e-9])
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": sum(busy.values()) / n * 1e-9,
        "busy_s_per_device": {d: v * 1e-9 for d, v in busy.items()},
        "idle_share": 1.0 - sum(busy.values()) / n / window_ns,
        "idle_in_program_share": idle_open / n / window_ns,
        "idle_with_stage_open_share": {k: v / n / window_ns
                                       for k, v in by_stage.items()},
        "kernel_s": {k: v * 1e-9 for k, v in kernel_ns.items()},
        "kernel_calls": kernel_calls,
        "devices": len(busy),
        "first_op_s": (min(a for a, _ in inside) - lo) * 1e-9 if inside
        else None,
        "last_op_s": (hi - max(b for _, b in inside)) * 1e-9 if inside
        else None,
        "breakdown": {"device_ops": [[k, v * 1e-9 / n] for k, v in ops],
                      "idle_gaps": idle_gaps},
    }
