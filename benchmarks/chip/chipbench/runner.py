"""A whole run: set-up, window, metrics, correctness, the result line."""
from __future__ import annotations

import tempfile
import time
from typing import List, Tuple

import numpy as np

from chipbench import flops, harness, manifest, trace_reduce, traffic


def configure_jax(cache: str) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", cache)
    # every program, however small, goes to the cache: later runs of the
    # cell then load what the first one compiled instead of compiling it
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def load_cell(name: str):
    man = manifest.load_manifest()
    cell = manifest.cell(man, name)
    cfg = manifest.config(man, cell["config"])
    mix = traffic.Mix.load(manifest.traffic_path(cell["traffic"]),
                           cell["traffic"])
    return man, cell, cfg, mix, manifest.reference(cfg)


def read_metrics(specs, window) -> dict:
    out = {}
    for spec in specs:
        value = manifest.metric_reader(spec["name"]).read(window)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def check(window, ref, cfg, seed, chips) -> Tuple[bool, dict]:
    """Compare a sample of the window's answers with the reference.  Every
    request due in the window must have been answered; the sampled answers
    must lie within the configuration's limits."""
    limits = cfg["limits"]
    unanswered = sum(1 for r in window.records if r.error is not None
                     or r.done is None)
    picked = harness.sample(window, seed)
    checks = {"unanswered": {"value": unanswered, "limit": 0}}
    if picked:
        vocab = cfg["vocab_size"]
        xs = np.concatenate([traffic.tokens(seed, r.i, r.rows, window.mix.seq,
                                            vocab) for r in picked])
        y = np.concatenate([r.y for r in picked])
        r = harness.reference_scores(ref, cfg, seed, chips, xs)
        for k, v in harness.compare(y, r).items():
            checks[k] = {"value": v, "limit": limits[k]}
    print(f"answers compared: {len(picked)} requests, "
          f"{sum(r.rows for r in picked)} rows", flush=True)
    ok = bool(picked) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks


def put_control(window, ref, cfg, seed, chips) -> None:
    """Put the correctness control in the program's place: every answer
    that :func:`check` compares becomes the reference's with its matrices
    rounded to int8, the precision below the configuration's bfloat16."""
    picked = harness.sample(window, seed)
    xs = np.concatenate([traffic.tokens(seed, r.i, r.rows, window.mix.seq,
                                        cfg["vocab_size"]) for r in picked])
    y = harness.reference_scores(ref, cfg, seed, chips, xs, weights="int8")
    k = 0
    for r in picked:
        r.y, k = y[k:k + r.rows], k + r.rows


def run(name: str, seed: int, seconds: float, traced: bool,
        t_start: float) -> Tuple[dict, List[str]]:
    traffic.check_seed(seed)
    man, cell, cfg, mix, ref = load_cell(name)
    configure_jax(manifest.cache_dir())
    log = harness.CompileLog()
    chips = harness.chips_for(int(cell["chips"]))
    import jax
    served = harness.build(cfg, ref, mix.seq, seed, chips)
    for d in chips:
        stats = d.memory_stats() or {}
        print(f"bytes in use after load: {d} {stats.get('bytes_in_use')} "
              f"of {stats.get('bytes_limit')}", flush=True)
    warm = harness.warm_up(served, mix, seed)
    setup_compiles = log.snapshot()
    print(f"warm-up: {warm} requests; set-up programs compiled or loaded "
          f"{setup_compiles}", flush=True)
    tdir = tempfile.TemporaryDirectory(prefix="chipbench-trace-") \
        if traced else None
    window = harness.measure(served, mix, seed, seconds, log,
                             tdir.name if tdir else None)
    window.setup_s = window.t0 - t_start
    window.setup_compiles = setup_compiles
    window.peak = flops.peaks(chips[0].device_kind)
    in_window = {k: window.compiles1[k] - window.compiles0[k]
                 for k in window.compiles0}
    print(f"programs compiled or loaded inside the window: "
          f"{in_window['compiles']} ({in_window['compile_s']:.3f} s; cache "
          f"{in_window['hits']} hits, {in_window['misses']} misses)",
          flush=True)
    recs = window.records
    late = [r.sent - r.due for r in recs if r.sent]
    if late:
        print(f"generator lateness: median {1e3 * float(np.median(late)):.3f}"
              f" ms, max {1e3 * max(late):.3f} ms", flush=True)
    failed = sum(1 for r in recs if r.error is not None or r.done is None)
    done_in = len(window.completed_in_window())
    print(f"requests: sent {len(recs)}, completed {len(recs) - failed} "
          f"({done_in} by the close), failed {failed}", flush=True)
    for r in recs:
        if r.error is not None:
            print(f"request {r.i} failed: {r.error}", flush=True)
            break
    peak_bytes = harness.memory_peak(chips)
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    breakdown = None
    if traced:
        t0 = time.perf_counter()
        trace = trace_reduce.load(trace_reduce.find_xplane(tdir.name))
        tdir.cleanup()
        window.trace = trace_reduce.reduce(trace, cfg.get("kernels"))
        print(f"device operations traced from {window.trace['first_op_s']!r}"
              f" s after the window opened to {window.trace['last_op_s']!r}"
              f" s before it closed", flush=True)
        device["busy_s"] = window.trace["busy_s"]
        device["window_s"] = window.trace["window_s"]
        breakdown = window.trace["breakdown"]
        print(f"trace read in {time.perf_counter() - t0:.1f} s: "
              f"{window.trace['devices']} devices, busy "
              f"{window.trace['busy_s']:.3f} of {window.trace['window_s']:.3f}"
              f" s, kernels {window.trace['kernel_s']} calls "
              f"{window.trace['kernel_calls']}", flush=True)
    kind = "per_layer" if traced else "end_to_end"
    metrics = read_metrics(manifest.metrics_of(man, name, kind), window)
    harness.release(served)
    t0 = time.perf_counter()
    correct, checks = check(window, ref, cfg, seed, chips)
    print(f"reference check: {time.perf_counter() - t0:.1f} s", flush=True)
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    lines = [f"check {k}: {c['value']!r} (limit {c['limit']!r})"
             for k, c in checks.items()]
    return result, lines
