"""Serve a full-width ensemble on the TPU through the normal entry point and
check the answers.

    python chip_smoke.py              # one chip: two qwen3-1.7b bf16 members
    python chip_smoke.py --chips 4    # four chips: four qwen3-1.7b fp32 members

Everything runs in this one process: allocation (analytic planner), the
InferenceSystem, the HTTP server, four ``/v2/predict`` requests of four rows
each, one after another (two at ``priority="high"``), and the
device-resident Pallas combine.
Every response is checked against a plain reference — each member's
``forward`` jitted directly on the chip its worker runs on, last-token
logits, weighted mean in NumPy — and the run fails on any degraded result,
crash, quarantine or dropped row.  Weights are random, made from ``--seed``.
The last line of standard output is one JSON object naming the device.
Without a TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import sys
import time
import urllib.error
import urllib.request

import numpy as np

ROWS, REQUESTS, SEQ = 4, 4, 128
# Requests go one at a time, so each one's rows run alone in one compiled
# batch (the bucket for ROWS rows, zero-padded), and the reference runs the
# same rows at the same shape.  At the chip's default matmul precision two
# batch shapes of one model differ by ~3e-2 in these logits; one shape
# agrees to float32 rounding of the weighted sum.
ATOL = RTOL = 1e-4


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port: int, payload: dict):
    """POST /v2/predict; returns (status, parsed body, seconds)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v2/predict",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=600) as r:
            status, body = r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        status, body = e.code, e.read().decode(errors="replace")
    return status, body, time.perf_counter() - t0


_REF_FNS: dict = {}       # (config name, wrapped params) -> jitted reference


def _reference(system, x: np.ndarray) -> np.ndarray:
    """Weighted mean of each member's last-token logits for the rows ``x``,
    every member run by a plain jitted ``forward`` on its own worker's chip
    and params, at the batch shape its worker ran them in."""
    import jax
    from repro.kernels.quant import dequantize_params
    from repro.models import forward
    from repro.serving.worker import bucket_for

    weights = np.asarray(system.accumulator.weights, np.float64)
    weights = weights / weights.sum()
    y = np.zeros((x.shape[0], system.num_classes), np.float64)
    for m, cfg in enumerate(system.cfgs):
        wrapped = system.member_dtypes[m] != "fp32"
        fn = _REF_FNS.get((cfg.name, wrapped))
        if fn is None:
            def last_logits(params, tokens, cfg=cfg, wrapped=wrapped):
                p = dequantize_params(params) if wrapped else params
                return forward(p, cfg, tokens)[0][:, -1, :cfg.vocab_size]
            fn = _REF_FNS[(cfg.name, wrapped)] = jax.jit(last_logits)
        worker = system.instances(m)[0]
        rows = np.zeros((bucket_for(len(x), worker.batch_size), x.shape[1]),
                        np.int32)
        rows[:len(x)] = x
        chip = worker.device.jax_devices[0]
        out = fn(worker.params, jax.device_put(rows, chip))
        y += weights[m] * np.asarray(out, np.float64)[:len(x)]
    return y


class _CompileLog:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events."""
    def __init__(self):
        import jax
        self.seconds, self.programs, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self):
        return (f"{self.seconds:.2f} s backend compile over {self.programs} "
                f"programs, persistent cache {self.hits} hits / "
                f"{self.misses} misses")


def _report_failures(system, workers) -> None:
    """Print what supervision contained: counters, and each worker's crash
    cause or stage heartbeats."""
    import traceback
    counters = system.serving_counters()
    print("counters: " + ", ".join(
        f"{k} {counters.get(k, 0)}" for k in (
            "worker_crashes", "stalls_detected", "quarantines",
            "segments_replayed", "rows_dropped")))
    for w in workers:
        print(f"{w.worker_id}: health {w.health(system.watchdog_s)}, "
              f"heartbeats {w._hb}")
        if w.crash_cause is not None:
            print("".join(traceback.format_exception(w.crash_cause)))


def _check_placement(system, chips: int) -> None:
    """Every worker's params live on the chip its allocation row names, and
    the members span ``chips`` chips."""
    import jax
    used = set()
    for w in system.workers:
        chip = system.alloc.devices[w.device_idx].jax_devices[0]
        held = {d for leaf in jax.tree_util.tree_leaves(w.params)
                for d in leaf.devices()}
        assert held == {chip}, (w.worker_id, chip, held)
        used.add(chip)
        print(f"placement: {w.worker_id} member {w.model_idx} batch "
              f"{w.batch_size} on {chip}")
    assert len(used) == chips, f"members use {len(used)} of {chips} chips"


def run(chips: int, seed: int) -> dict:
    import jax
    from repro.compile_cache import enable_compile_cache
    from repro.kernels import ops
    from repro.launch import serve

    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}")
    compiles = _CompileLog()
    if chips == 1:
        flags = ["--members", "2", "--member-dtype", "bf16"]
    else:
        flags = ["--members", str(chips), "--member-dtype", "fp32"]
    port = _free_port()
    args = serve.parse_args(
        ["--ensemble", "qwen3-1.7b", *flags, "--seq", str(SEQ),
         "--segment-size", "32", "--combine", "pallas", "--bench", "analytic",
         "--port", str(port)])
    t0 = time.perf_counter()
    srv = serve.start_serving(args, alloc_cache=None, seed=seed)
    setup = time.perf_counter() - t0
    try:
        system = srv.system
        print(f"setup seconds: total {setup:.2f} "
              + " ".join(f"{k} {v:.2f}" for k, v in srv.setup_s.items())
              + " (system = H2D + warm-up compile)")
        print(f"set-up compile: {compiles}")
        for d in jax.devices():
            stats = d.memory_stats() or {}
            print(f"bytes in use after load: {d} "
                  f"{stats.get('bytes_in_use')} of {stats.get('bytes_limit')}")
        print(f"pallas_enabled: {ops.pallas_enabled()}")
        assert ops.pallas_enabled(), "Pallas kernels would run interpreted"
        if chips > 1:
            _check_placement(system, chips)

        workers = list(system.workers)   # kept past a quarantine
        rng = np.random.default_rng(seed)
        vocab = system.num_classes
        xs = [rng.integers(0, vocab, (ROWS, SEQ)).astype(np.int32)
              for _ in range(REQUESTS)]
        errs, decided, agree = [], 0, 0
        for i, x in enumerate(xs):
            priority = "high" if i % 2 else "normal"
            status, body, dt = _post(port, {"tokens": x.tolist(),
                                            "priority": priority})
            print(f"request {i} ({priority}): {dt * 1e3:.1f} ms, "
                  f"status {status}")
            if status != 200:
                _report_failures(system, workers)
            assert status == 200, (i, status, body)
            assert body.get("quality", 1.0) == 1.0, (i, body.get("quality"))
            y = np.asarray(body["predictions"], np.float64)
            assert y.shape == (ROWS, vocab), (i, y.shape)
            assert np.isfinite(y).all(), i
            ref = _reference(system, x)
            errs.append(float(np.abs(y - ref).max()))
            np.testing.assert_allclose(y, ref, atol=ATOL, rtol=RTOL)
            # argmax must agree wherever the reference's top-1/top-2 margin
            # is wider than the tolerance (a closer tie is rounding's call)
            top2 = np.sort(ref, axis=1)[:, -2:]
            wide = top2[:, 1] - top2[:, 0] > 2 * ATOL
            same = y.argmax(1) == ref.argmax(1)
            assert same[wide].all(), (i, y.argmax(1), ref.argmax(1))
            decided += int(wide.sum())
            agree += int(same.sum())
        print(f"max abs error vs reference: {max(errs):.3e} "
              f"(per request {', '.join(f'{e:.3e}' for e in errs)})")
        print(f"argmax agrees on {agree}/{ROWS * REQUESTS} rows "
              f"({decided} with a top-2 margin above {2 * ATOL:g})")

        print(f"compile after set-up, requests and reference included: "
              f"{compiles}")
        counters = system.serving_counters()
        for name in ("worker_crashes", "stalls_detected", "quarantines",
                     "rows_dropped"):
            print(f"{name}: {counters.get(name, 0)}")
            assert counters.get(name, 0) == 0, (name, counters.get(name))
        ctl = srv.controller
        spawn_failures = ctl.counters["spawn_failures"] if ctl else 0
        print(f"spawn_failures: {spawn_failures}")
        assert spawn_failures == 0
    finally:
        srv.close()
    dev = jax.devices()[0]
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": len(jax.devices())}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the four-chip phase: one fp32 member "
                         "per chip, checked against a per-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (devices: {devices})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    result = run(args.chips, args.seed)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
