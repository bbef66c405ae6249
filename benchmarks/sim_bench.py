"""Discrete-event serving-simulator benchmarks (ISSUE 8, DESIGN.md §12).

The simulator (``repro.serving.sim``) runs the *real* policy code —
admission ordering, the chunk-granular dispatch queue, work stealing,
brownout, LiveBench, the bounded-greedy replanner — under a virtual clock
with per-member service-time models, so serving questions that would take
minutes of wall time (and a noisy host) resolve in seconds, exactly
reproducibly.  Scenarios:

  * ``scale``       replay throughput: a Poisson trace through a 4-worker
                    system, single process.  Default 250k requests in CI
                    (``SIM_SCALE_REQUESTS=1000000`` reproduces the
                    acceptance demonstration); ``scale_ok`` gates the
                    ISSUE-8 bar — a 1M-request replay must fit in 60 s, so
                    the measured rate must hold >= 1e6/60 req/s —
                    plus full completion.  The same pass replays a 20k
                    prefix twice and diffs the event logs + results for
                    the bit-identical determinism guarantee
                    (``determinism_ok``);
  * ``forecast_replan``  the planning workload (ROADMAP item j): diurnal
                    antiphase demand across two members on three devices
                    at ~0.8 mean utilization — each half-cycle the hot
                    member needs 2 of the 3 devices, so a replanner fed a
                    *trailing* demand EWMA flips allocations after the
                    wave has already built backlog.  Runs the identical
                    trace with the bounded greedy scoring the LiveBench
                    EWMA vs the linear-trend forecaster feeding
                    ``LiveBench.set_forecast`` ahead of each replan;
                    ``p99_improvement`` (EWMA p99 / forecast p99) gates
                    that planning against *predicted* shares beats
                    planning against trailing ones;
  * ``ktuner``      the dispatch-ahead auto-tuner (ROADMAP item l) on a
                    saturated bulk trace with per-group overhead h=0.2 ms
                    and per-chunk service s=1.0 ms: throughput follows
                    K/(h + K*s), and the smallest K within 1% of the best
                    is 16 — the tuner must reproduce the live engine's
                    known-good ``DISPATCH_AHEAD`` default
                    (``recommended_ok``);
  * ``edf``         the prototype chunk-level EDF scheduler (ROADMAP item
                    m, ``EDFDispatchQueue``): bursts sized to the ring
                    window where two tight-deadline requests arrive buried
                    behind loose ones.  FIFO serves them in arrival order
                    and misses; EDF pops earliest-absolute-deadline chunks
                    first and meets every deadline on the identical trace
                    (``miss_reduction`` = 1 - EDF misses / FIFO misses).

Acceptance (ISSUE 8): >= 1M synthetic requests replay in < 60 s
single-process (``scale.scale_ok``); forecast-fed replanning beats
EWMA-fed on the diurnal trace (``forecast_replan.p99_improvement``, floor
1.2x); the tuner reproduces K=16 on the throughput trace
(``ktuner.recommended_ok``).
"""
from __future__ import annotations

import os
import time

import numpy as np

from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.core.greedy import bounded_greedy
from repro.serving.admission import EDFDispatchQueue
from repro.serving.control import LiveBench
from repro.serving.sim import (DemandForecaster, ServiceModel, SimSystem,
                               WorkerSpec, diurnal_trace, poisson_trace,
                               tune_dispatch_ahead)
from repro.serving.trace import TraceEvent

GiB = 1024 ** 3

# the ISSUE-8 scale bar: 1M requests in < 60 s single-process
SCALE_RATE_FLOOR = 1e6 / 60.0


def _scale_system(svc):
    return SimSystem(svc, [WorkerSpec(0, 64), WorkerSpec(0, 64),
                           WorkerSpec(1, 64), WorkerSpec(1, 64)],
                     segment_size=64, max_wait_us=500.0)


def _measure_scale(requests: int, seed: int) -> dict:
    """Replay throughput + the bit-identical determinism guarantee."""
    svc = ServiceModel.from_delays({0: 200, 1: 200})
    trace = poisson_trace(requests, rate=120_000.0, seed=seed, rows=8,
                          members_choices=[(0,), (1,)])
    sim = _scale_system(svc)
    t0 = time.perf_counter()
    sim.run(trace)
    dt = time.perf_counter() - t0
    r = sim.results()
    rate = requests / dt
    out = {
        "requests": requests,
        "replay_seconds": dt,
        "replay_req_per_s": rate,
        "completed": r["completed"],
        "failed": r["failed"],
        "p99_ms": r["p99_ms"],
        "scale_ok": float(rate >= SCALE_RATE_FLOOR and
                          r["completed"] == requests),
    }
    # determinism: same seed + trace -> bit-identical event log and results
    logs, metrics = [], []
    for _ in range(2):
        s2 = SimSystem(svc, [WorkerSpec(0, 64), WorkerSpec(0, 64),
                             WorkerSpec(1, 64), WorkerSpec(1, 64)],
                       segment_size=64, max_wait_us=500.0,
                       record_events=True)
        s2.run(trace[:20_000])
        logs.append(tuple(s2.event_log))
        metrics.append(s2.results())
    out["determinism_ok"] = float(logs[0] == logs[1]
                                  and metrics[0] == metrics[1])
    out["determinism_events"] = len(logs[0])
    return out


def _measure_forecast_replan(seed: int) -> dict:
    """EWMA-fed vs forecast-fed bounded-greedy replanning on the identical
    diurnal trace.  Each (member, device) placement is pre-calibrated into
    the LiveBench so the greedy scores every neighbour from measurements
    (a cold placement would fall back to the analytic roofline, which has
    nothing to do with the simulated service model)."""
    cfgs = ensemble("ENS4")[:2]
    devs = host_cpus(3, memory_bytes=8 * GiB)
    A0 = np.array([[64, 0], [64, 0], [0, 64]])
    svc = ServiceModel.from_delays({0: 4000, 1: 4000})
    # 3 devices x 16k rows/s, mean offered 4800 req/s x 8 rows = 0.8 util;
    # amplitude 0.4 swings each member between 10% and 90% of demand
    trace = diurnal_trace(19_200, seed=seed, rate=4800.0, period_s=2.0,
                          amplitude=0.4, rows=8,
                          members_groups=((0,), (1,)))
    out = {}
    for mode in ("ewma", "forecast"):
        alloc = AllocationMatrix(devs, [c.name for c in cfgs], A0.copy())
        live = LiveBench(cfgs, seq=16)
        for m in range(len(cfgs)):
            for d in devs:
                for _ in range(8):
                    live.observe(m, d.key(), 64, 64, 0.004)
        sim = SimSystem.from_alloc(alloc, svc, segment_size=64, live=live,
                                   max_wait_us=500)
        fc = DemandForecaster(len(cfgs), bin_s=0.1, trend_bins=4)
        if mode == "forecast":
            sim.forecaster = fc
        applied = [0]

        def replan(s, fc=fc, live=live, mode=mode, applied=applied):
            if mode == "forecast":
                fc.feed(live, lead_s=0.35, ttl_s=0.6)
            prop, _ = bounded_greedy(s.alloc, live, max_iter=3,
                                     max_neighs=60, batch_sizes=(64,),
                                     seed=0)
            if live(prop) > live(s.alloc) * 1.005:
                s.apply_alloc(prop)
                applied[0] += 1

        sim.add_control(0.25, replan, phase_s=0.25)
        sim.run(trace)
        r = sim.results()
        out[mode] = {"p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
                     "completed": r["completed"], "failed": r["failed"],
                     "replans_applied": applied[0],
                     "throughput_rows_per_s": r["throughput_rows_per_s"]}
    out["p99_improvement"] = (out["ewma"]["p99_ms"] /
                              max(out["forecast"]["p99_ms"], 1e-9))
    out["p50_improvement"] = (out["ewma"]["p50_ms"] /
                              max(out["forecast"]["p50_ms"], 1e-9))
    return out


def _measure_ktuner(seed: int) -> dict:
    """Sweep the dispatch-ahead window on a saturated bulk trace; the
    throughput objective must land on the live default (16)."""
    svc = ServiceModel.from_delays({0: 1000},
                                   dispatch_overhead_s=2e-4)
    trace = poisson_trace(400, rate=1e6, seed=seed, rows=64,
                          members_choices=[(0,)])

    def make_sim(k):
        return SimSystem(svc, [WorkerSpec(0, 8)], segment_size=64,
                         dispatch_ahead=k, max_wait_us=100)

    out = tune_dispatch_ahead(make_sim, trace, ks=(1, 2, 4, 8, 16, 32))
    out["recommended_ok"] = float(out["recommended"] == 16)
    return out


def _measure_edf() -> dict:
    """Deadline-mixed bursts through the FIFO dispatch queue vs the EDF
    prototype; the trace is deterministic by construction (no RNG)."""
    svc = ServiceModel.from_delays({0: 2000})
    events = []
    for b in range(40):
        t = b * 0.012          # 8 ms of service every 12 ms: drains fully
        for i in range(4):     # burst fits the 4-slot ring window
            dl = 7.0 if i >= 2 else 400.0
            events.append(TraceEvent(t=t + i * 1e-5, rows=64,
                                     deadline_ms=dl, members=(0,)))
    out = {}
    for name, qcls in (("fifo", None), ("edf", EDFDispatchQueue)):
        kw = {"queue_cls": qcls} if qcls else {}
        sim = SimSystem(svc, [WorkerSpec(0, 64)], segment_size=64,
                        dispatch_ahead=1, max_wait_us=100, **kw)
        sim.run(events)
        r = sim.results()
        out[name] = {"completed": r["completed"], "failed": r["failed"],
                     "deadline_misses": r["deadline_misses"],
                     "p99_ms": r["p99_ms"]}
    fifo, edf = (out["fifo"]["deadline_misses"],
                 out["edf"]["deadline_misses"])
    out["miss_reduction"] = (1.0 - edf / fifo) if fifo else 0.0
    return out


def run(csv=True, scale_requests=None, seed=7):
    if scale_requests is None:
        scale_requests = int(os.environ.get("SIM_SCALE_REQUESTS", 250_000))
    results = {"rng_seed": seed}
    results["scale"] = _measure_scale(scale_requests, seed)
    results["forecast_replan"] = _measure_forecast_replan(seed + 14)
    results["ktuner"] = _measure_ktuner(seed + 6)
    results["edf"] = _measure_edf()

    if csv:
        sc = results["scale"]
        print(f"sim:scale.replay_req_per_s,{sc['replay_req_per_s']:.0f},"
              f"{sc['requests']}")
        print(f"sim:scale.scale_ok,{sc['scale_ok']:.0f},"
              f"floor={SCALE_RATE_FLOOR:.0f}")
        print(f"sim:scale.determinism_ok,{sc['determinism_ok']:.0f},"
              f"{sc['determinism_events']}")
        fr = results["forecast_replan"]
        for mode in ("ewma", "forecast"):
            r = fr[mode]
            print(f"sim:forecast_replan.{mode}.p50/p99_ms,"
                  f"{r['p50_ms']:.1f},{r['p99_ms']:.1f}")
        print(f"sim:forecast_replan.p99_improvement,"
              f"{fr['p99_improvement']:.2f},")
        kt = results["ktuner"]
        print(f"sim:ktuner.recommended,{kt['recommended']},"
              f"ok={kt['recommended_ok']:.0f}")
        ed = results["edf"]
        print(f"sim:edf.misses_fifo/edf,{ed['fifo']['deadline_misses']},"
              f"{ed['edf']['deadline_misses']}")
        print(f"sim:edf.miss_reduction,{ed['miss_reduction']:.2f},")
    return results


if __name__ == "__main__":
    run()
