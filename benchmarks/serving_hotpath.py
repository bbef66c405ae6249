"""Replay a recorded request trace against a fake-device serving system.

    PYTHONPATH=src python -m benchmarks.serving_hotpath --replay-trace PATH \
        [--replay-speed X]

The trace is one that ``launch/serve.py --record-trace`` (or
``system.trace_recorder``) wrote.  Each request is submitted with its
recorded priority, deadline and member subset, paced by the recorded
inter-arrival gaps, to an ``InferenceSystem`` of fake workers on one CPU
device, so the serving machinery (admission, batching, combine) runs
without model compute.  Prints completed requests per second and the
p50/p99 latency.  These are host-clock numbers of the serving machinery,
not device speeds: speed is measured on the chip by ``benchmarks/chip``.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.serving.segments import PredictOptions

GiB = 1024 ** 3


def _pctl(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def replay_trace(path: str, *, seq: int = 16, workers: int = 2,
                 speed: float = 1.0) -> dict:
    """Replay a recorded request trace (``--record-trace`` /
    ``system.trace_recorder``) against a real fake-device system,
    preserving per-request priority, deadline and member subsets and
    pacing submissions by the recorded inter-arrival gaps (divided by
    ``speed``).  The offline twin of the simulator's ``sim.run(trace)``."""
    import jax
    import repro.models as M
    from repro.serving.system import InferenceSystem
    from repro.serving.trace import load_trace

    events = load_trace(path)
    cfgs = ensemble("ENS4")[:workers]
    rng = jax.random.PRNGKey(0)
    params = [M.init_params(jax.random.fold_in(rng, i), c)
              for i, c in enumerate(cfgs)]
    devs = host_cpus(1, memory_bytes=8 * GiB)
    A = np.full((1, len(cfgs)), 64)
    alloc = AllocationMatrix(devs, [c.name for c in cfgs], A)
    srng = np.random.default_rng(0)
    lat: list = []
    lock = threading.Lock()
    failed = 0
    with InferenceSystem(cfgs, params, alloc, segment_size=64, max_seq=seq,
                         fake=True, max_in_flight=max(64, len(events)),
                         max_wait_us=500) as system:
        system.predict(srng.integers(0, 512, (8, seq)).astype(np.int32))

        def waiter(h, t1):
            nonlocal failed
            try:
                h.result(600.0)
            except Exception:
                with lock:
                    failed += 1
                return
            with lock:
                lat.append(time.perf_counter() - t1)

        threads = []
        t0 = time.perf_counter()
        for ev in events:
            target = t0 + ev.t / speed
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            X = srng.integers(0, 512, (ev.rows, seq)).astype(np.int32)
            members = None if ev.members is None else list(ev.members)
            opts = PredictOptions(priority=ev.priority,
                                  deadline_ms=ev.deadline_ms)
            try:
                h = system.predict_async(X, members=members, options=opts)
            except Exception:
                with lock:
                    failed += 1
                continue
            th = threading.Thread(target=waiter,
                                  args=(h, time.perf_counter()))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
        dt = time.perf_counter() - t0
    out = {"trace": path, "requests": len(events), "speed": speed,
           "seconds": dt, "completed": len(lat), "failed": failed,
           "req_per_s": len(lat) / dt,
           "p50_ms": 1e3 * _pctl(lat, 50) if lat else 0.0,
           "p99_ms": 1e3 * _pctl(lat, 99) if lat else 0.0}
    print(f"serving_hotpath:replay.req_per_s,{out['req_per_s']:.1f},")
    print(f"serving_hotpath:replay.p50/p99_ms,{out['p50_ms']:.1f},"
          f"{out['p99_ms']:.1f}")
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="replay a recorded request trace against a "
                    "fake-device serving system")
    ap.add_argument("--replay-trace", required=True, metavar="PATH",
                    help="a request trace (launch/serve.py --record-trace "
                         "or system.trace_recorder)")
    ap.add_argument("--replay-speed", type=float, default=1.0,
                    help="time-compression factor for the recorded gaps")
    args = ap.parse_args(argv)
    replay_trace(args.replay_trace, speed=args.replay_speed)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
