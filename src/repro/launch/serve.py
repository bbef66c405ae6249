"""Serving launcher: optimize an ensemble allocation over device cells and
start the inference server.

On a TPU host every chip is one allocation cell (core.devices.tpu_cells) and
each worker runs on the chip its allocation row names; with
``JAX_PLATFORMS=cpu`` the same path runs on host CPU cells.

    python -m repro.launch.serve --ensemble ENS4 --port 8600
    python -m repro.launch.serve --ensemble qwen3-1.7b --members 2 \
        --member-dtype bf16 --seq 128 --combine pallas --bench analytic
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

ALLOC_CACHE = ".repro_alloc_cache.json"


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ensemble", default="ENS4",
                    help="ENS1|ENS4|ENS12, or one architecture name (e.g. "
                         "qwen3-1.7b) served at its published widths as "
                         "--members independently initialised members")
    ap.add_argument("--members", type=int, default=0,
                    help="first N members of the ensemble; copies of an "
                         "architecture (default 1)")
    ap.add_argument("--cells", type=int, default=2,
                    help="host CPU cells (JAX_PLATFORMS=cpu only; on a TPU "
                         "host every chip is one cell)")
    ap.add_argument("--cell-mem-gib", type=float, default=4.0,
                    help="memory budget of each host CPU cell")
    ap.add_argument("--port", type=int, default=8600)
    ap.add_argument("--segment-size", type=int, default=32)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--combine", default="mean")
    ap.add_argument("--member-dtype", default="fp32",
                    help="member execution precision (DESIGN.md §14): one "
                         "value for all members (fp32|bf16|int8|fp8) or a "
                         "comma-separated per-member list, e.g. "
                         "'int8,int8,fp32,fp32'.  Quantized members store "
                         "params narrow (per-output-channel scales), pack "
                         "~2x denser in the allocator, and feed the fused "
                         "dequant-combine epilogue")
    ap.add_argument("--dispatch-queue", default="fifo",
                    choices=("fifo", "edf"),
                    help="predictor dispatch order: fifo = strict priority "
                         "then arrival; edf = earliest-deadline-first "
                         "within priority class (simulator-validated, "
                         "DESIGN.md §12)")
    ap.add_argument("--bench", default="measured", choices=("measured", "analytic"))
    ap.add_argument("--duration", type=float, default=0.0,
                    help="serve for N seconds then exit (0 = forever)")
    ap.add_argument("--linger", default="fixed", choices=("fixed", "adaptive"),
                    help="adaptive scales the coalescing linger down with "
                         "queue depth (DESIGN.md §7)")
    ap.add_argument("--max-wait-us", type=int, default=500,
                    help="coalescing linger bound per open batch slot")
    ap.add_argument("--dispatch-ahead", type=int, default=0,
                    help="committed (non-preemptible) chunk window per "
                         "worker: small (1-2) favors high-priority latency, "
                         "large favors throughput; 0 = library default "
                         "(DESIGN.md §3)")
    ap.add_argument("--cache-capacity", type=int, default=0,
                    help="rows in the prediction cache (0 disables)")
    ap.add_argument("--reconfig", action="store_true",
                    help="run the online reconfiguration controller: live "
                         "replanning against the EWMA workload profile plus "
                         "cross-worker work stealing (DESIGN.md §8)")
    ap.add_argument("--reconfig-interval", type=float, default=5.0,
                    help="seconds between live replans (with --reconfig)")
    ap.add_argument("--steal-threshold", type=int, default=4,
                    help="queue-depth gap between data-parallel siblings "
                         "that triggers work stealing (with --reconfig)")
    ap.add_argument("--no-steal", action="store_true",
                    help="disable the work-stealing fast path (replanning "
                         "only, with --reconfig)")
    # fault tolerance (DESIGN.md §10)
    ap.add_argument("--no-supervise", action="store_true",
                    help="disable worker supervision and fall back to the "
                         "paper's all-or-nothing failure model: any worker "
                         "crash fails every in-flight request and shuts the "
                         "system down (§II.C.2)")
    ap.add_argument("--watchdog-s", type=float, default=5.0,
                    help="a worker stage mid-work longer than this is "
                         "declared stalled and its instance quarantined")
    ap.add_argument("--retry-budget", type=int, default=2,
                    help="max times one request's chunks may be resubmitted "
                         "after worker failures before it fails with "
                         "RetriesExhausted (HTTP 503)")
    ap.add_argument("--nan-guard", action="store_true",
                    help="check materialized device outputs for NaN; a "
                         "poisoned output crashes its worker (quarantine + "
                         "replay on a sibling) instead of folding into Y")
    ap.add_argument("--fault", action="append", default=[],
                    metavar="SPEC",
                    help="inject a deterministic fault for chaos testing; "
                         "repeatable.  SPEC is key=value pairs: "
                         "stage=batcher|predictor|sender|spawn "
                         "[kind=raise|stall|nan|slow] [after=N] [stall_s=S] "
                         "[repeat=true] [worker=ID-prefix], e.g. "
                         "--fault stage=predictor,after=100,worker=w0.0 or "
                         "a sustained overload drill: "
                         "--fault stage=predictor,kind=slow,stall_s=0.004")
    # overload robustness (DESIGN.md §11)
    ap.add_argument("--brownout", action="store_true",
                    help="run the brownout controller: continuous pressure "
                         "signal from queue depths / p99 / loss counters, "
                         "hysteresis into discrete levels, each serving a "
                         "cheaper member-subset quality tier; plus cost-"
                         "aware admission (429 + computed Retry-After on "
                         "infeasible deadlines)")
    ap.add_argument("--tier-table", default=None,
                    help="explicit brownout tiers as semicolon-separated "
                         "member-id lists, level 0 first, e.g. "
                         "'0,1,2;0,1;0'; default derives tiers from "
                         "per-member cost/weight ratios (EARN-style)")
    ap.add_argument("--brownout-deadline-ms", type=float, default=None,
                    help="latency budget the pressure signal compares the "
                         "normal-class p99 against (default: none — queue "
                         "depth and loss counters drive pressure)")
    ap.add_argument("--cascade-margin", type=float, default=None,
                    help="confidence-gated cascade: tier results whose "
                         "top1-top2 margin falls below this escalate to the "
                         "dropped members (with --brownout)")
    # simulation / planning (DESIGN.md §12)
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="append every offered request to PATH as JSONL "
                         "(t, rows, priority, deadline_ms, members) for "
                         "offline replay: benchmarks/serving_hotpath.py "
                         "--replay-trace or the discrete-event simulator "
                         "(repro.serving.sim)")
    ap.add_argument("--admission-budget-mib", type=float, default=0.0,
                    help="global in-flight input-byte budget; requests "
                         "beyond it are refused with 429 + Retry-After "
                         "instead of queuing unboundedly (0 disables)")
    # observability / tracing (DESIGN.md §13)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing and write the flight "
                         "recorder as Chrome-trace / Perfetto JSON to PATH "
                         "at shutdown (also live at GET /v2/trace); open "
                         "it at https://ui.perfetto.dev")
    ap.add_argument("--flight-recorder", type=int, default=0, metavar="N",
                    help="per-track flight-recorder ring capacity in "
                         "events (enables tracing without --trace-out; "
                         "anomalies — watchdog stalls, deadline-miss "
                         "bursts, brownout shifts, exhausted retries — "
                         "freeze tagged dumps at GET /v2/trace?dumps=1; "
                         "0 = off unless --trace-out, default ring 4096)")
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    return build_parser().parse_args(argv)


def member_configs(name: str, members: int = 0) -> list:
    """An ensemble by name, or ``members`` copies of one architecture at its
    published widths (each initialised from its own seed)."""
    from repro.configs import ARCHITECTURES, ensemble
    if name in ARCHITECTURES:
        return [ARCHITECTURES[name]] * max(1, members)
    cfgs = ensemble(name)
    return cfgs[:members] if members else cfgs


def wanted_platform() -> str:
    """TPU cells, unless JAX has been restricted to the CPU."""
    import jax
    return "cpu" if jax.config.jax_platforms == "cpu" else "tpu"


def serving_devices(platform: str, cells: int = 2,
                    cell_mem_gib: float = 4.0) -> list:
    """Allocation cells: one per TPU chip, or ``cells`` host CPU cells.
    Asking for TPU cells where JAX finds no TPU is an error."""
    import jax
    from repro.core import host_cpus, tpu_cells
    if platform == "cpu":
        return host_cpus(cells, memory_bytes=int(cell_mem_gib * 1024 ** 3))
    chips = [d for d in jax.devices() if d.platform == platform]
    if not chips:
        raise RuntimeError(
            f"asked for {platform} cells, but JAX finds none (devices: "
            f"{[str(d) for d in jax.devices()]}); set JAX_PLATFORMS=cpu to "
            f"serve on host CPU cells")
    return tpu_cells(chips, 1)


def init_member_params(cfgs, member_dtypes, seed: int = 0) -> list:
    """Random weights for each member from ``seed``, made on the host CPU
    device in the member's storage dtype (bf16 members straight into bf16):
    workers then copy them to their own chips, and no chip holds a copy
    that no worker uses."""
    import jax
    import jax.numpy as jnp
    import repro.models as M
    rng = jax.random.PRNGKey(seed)
    with jax.default_device(jax.devices("cpu")[0]):
        return [M.init_params(jax.random.fold_in(rng, i), c,
                              jnp.bfloat16 if dt == "bf16" else jnp.float32)
                for i, (c, dt) in enumerate(zip(cfgs, member_dtypes))]


@dataclass
class Serving:
    """A running server and what it owns; :meth:`close` tears it down."""
    args: argparse.Namespace
    system: object
    httpd: object
    batcher: object
    controller: object = None
    brownout: object = None
    recorder: object = None
    setup_s: Dict[str, float] = field(default_factory=dict)

    def close(self) -> None:
        self.httpd.shutdown()
        self.httpd.server_close()
        self.batcher.stop()
        self.system.shutdown()
        args = self.args
        if self.recorder is not None:
            self.recorder.close()
            print(f"trace: {len(self.recorder.events())} requests recorded "
                  f"to {args.record_trace}")
        if args.trace_out:
            import json
            trace = self.system.tracer.export()
            with open(args.trace_out, "w") as f:
                json.dump(trace, f)
            print(f"span timeline: {len(trace['traceEvents'])} events "
                  f"written to {args.trace_out} (open at "
                  f"https://ui.perfetto.dev)")


def start_serving(args: argparse.Namespace, *,
                  alloc_cache: Optional[str] = ALLOC_CACHE,
                  seed: int = 0) -> Serving:
    """Members, dtypes, devices (:func:`wanted_platform`), planner,
    :class:`InferenceSystem` and the HTTP server, from parsed CLI ``args``.
    ``alloc_cache=None`` plans from scratch instead of reading a cached
    allocation."""
    import numpy as np
    from repro.core import AllocationOptimizer, AnalyticBench, MeasuredBench
    from repro.kernels.quant import validate_member_dtype
    from repro.serving.request_cache import PredictionCache
    from repro.serving.server import serve
    from repro.serving.system import InferenceSystem

    setup_s: Dict[str, float] = {}
    t0 = time.perf_counter()
    cfgs = member_configs(args.ensemble, args.members)
    dts = [d.strip() for d in args.member_dtype.split(",") if d.strip()]
    if len(dts) == 1:
        dts = dts * len(cfgs)
    if len(dts) != len(cfgs):
        raise ValueError(f"--member-dtype expects 1 or {len(cfgs)} values, "
                         f"got {len(dts)}")
    member_dtypes: List[str] = [validate_member_dtype(d) for d in dts]
    devices = serving_devices(wanted_platform(), args.cells,
                              args.cell_mem_gib)
    params = init_member_params(cfgs, member_dtypes, seed)
    setup_s["init"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    if args.bench == "measured":
        calib = np.random.default_rng(seed).integers(
            0, cfgs[0].vocab_size, (64, args.seq)).astype(np.int32)
        bench = MeasuredBench(cfgs, params, calib,
                              segment_size=args.segment_size)
        opt = AllocationOptimizer(cfgs, devices, bench, max_iter=1,
                                  max_neighs=4, batch_sizes=(8, 16),
                                  seq=args.seq, cache_path=alloc_cache,
                                  member_dtypes=member_dtypes)
    else:
        bench = AnalyticBench(cfgs, seq=args.seq,
                              member_dtypes=member_dtypes)
        opt = AllocationOptimizer(cfgs, devices, bench, max_iter=10,
                                  max_neighs=100, seq=args.seq,
                                  cache_path=alloc_cache,
                                  member_dtypes=member_dtypes)
    res = opt.optimize()
    setup_s["plan"] = time.perf_counter() - t0
    print("allocation matrix:\n" + res.matrix.pretty())
    print(f"bench: A1={res.wfd_score:.1f} -> A2={res.final_score:.1f} "
          f"samples/s{' (cached)' if res.from_cache else ''}")

    fault_plan = None
    if args.fault:
        from repro.serving.faults import FaultPlan, FaultSpec
        fault_plan = FaultPlan(*[FaultSpec.parse(s) for s in args.fault])
        print(f"fault injection armed: {args.fault}")
    budget = None
    if args.admission_budget_mib:
        from repro.serving.admission import AdmissionBudget
        budget = AdmissionBudget(
            max_bytes=int(args.admission_budget_mib * 1024 ** 2))
    trace_cap = args.flight_recorder or (4096 if args.trace_out else 0)
    t0 = time.perf_counter()
    system = InferenceSystem(cfgs, params, res.matrix,
                             segment_size=args.segment_size,
                             max_seq=args.seq, combine=args.combine,
                             max_wait_us=args.max_wait_us,
                             linger=args.linger,
                             dispatch_ahead=args.dispatch_ahead or None,
                             supervise=not args.no_supervise,
                             watchdog_s=args.watchdog_s,
                             retry_budget=args.retry_budget,
                             nan_guard=args.nan_guard,
                             fault_plan=fault_plan,
                             admission_budget=budget,
                             tracing=trace_cap > 0,
                             trace_capacity=trace_cap or 4096,
                             member_dtypes=member_dtypes,
                             dispatch_queue=args.dispatch_queue)
    setup_s["system"] = time.perf_counter() - t0
    if any(d != "fp32" for d in member_dtypes):
        print(f"member dtypes: {','.join(member_dtypes)} (quantized members "
              f"run the fused dequant-combine epilogue)")
    if args.dispatch_queue != "fifo":
        print(f"dispatch queue: {args.dispatch_queue}")
    if trace_cap:
        print(f"span tracing on (flight recorder {trace_cap} events/track; "
              f"GET /v2/trace, anomaly dumps at ?dumps=1)")
    if not args.no_supervise:
        print(f"supervision on (watchdog {args.watchdog_s:.1f}s, retry "
              f"budget {args.retry_budget}); worker failures quarantine the "
              f"instance — health gauges in GET /metrics")
    controller = None
    if args.reconfig:
        from repro.serving.control import ReconfigController
        controller = ReconfigController(
            system, interval_s=args.reconfig_interval,
            steal_threshold=args.steal_threshold,
            steal=not args.no_steal, batch_sizes=(8, 16, 32)).start()
        print(f"reconfig controller on (replan every "
              f"{args.reconfig_interval:.1f}s, steal "
              f"{'off' if args.no_steal else 'on'}; see GET /metrics "
              f"'controller')")
    brownout = None
    if args.brownout:
        from repro.serving.control import BrownoutController
        tiers = None
        if args.tier_table:
            tiers = [tuple(int(m) for m in level.split(","))
                     for level in args.tier_table.split(";") if level.strip()]
        brownout = BrownoutController(
            system, tiers=tiers,
            deadline_budget_ms=args.brownout_deadline_ms,
            cascade_margin=args.cascade_margin).start()
        print(f"brownout controller on ({len(brownout.tiers())} quality "
              f"tiers; see GET /metrics 'brownout')")
    if budget is not None:
        print(f"admission budget: {args.admission_budget_mib:.1f} MiB "
              f"in-flight input bytes (429 + Retry-After beyond it)")
    recorder = None
    if args.record_trace:
        from repro.serving.trace import TraceRecorder
        recorder = TraceRecorder(path=args.record_trace)
        system.trace_recorder = recorder
        print(f"recording request trace to {args.record_trace}")
    cache = PredictionCache(args.cache_capacity) if args.cache_capacity else None
    httpd, batcher = serve(system, port=args.port, cache=cache)
    print(f"serving {len(cfgs)} models / {len(system.workers)} workers on "
          f"http://127.0.0.1:{args.port}  (POST /v2/predict with priority/"
          f"deadline_ms/members, GET /metrics; POST /predict = v1 shim)")
    return Serving(args, system, httpd, batcher, controller, brownout,
                   recorder, setup_s)


def main(argv=None):
    from repro.compile_cache import enable_compile_cache
    args = parse_args(argv)
    print(f"compile cache: {enable_compile_cache()}")
    srv = start_serving(args)
    try:
        if args.duration:
            time.sleep(args.duration)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        srv.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
