"""One run of one cell: set-up, the measured window, the correctness check.

Set-up follows ``repro.launch.serve.start_serving``: the planner
(``AllocationOptimizer`` with ``AnalyticBench``, no allocation cache), then
``InferenceSystem`` with the serving CLI's defaults (device Pallas combine,
supervision on), then the in-process ``EnsembleClient`` -- the object that
``/v2/predict`` itself calls.  It departs in three ways: each member's
weights are made by one jitted call on the chip of the member's first
worker, from the seed; every row bucket and every way the traffic can cut a
request at compiled-batch boundaries is warmed by requests before the
window; and JAX's compilation cache stays at a fixed path.

The window drives ``EnsembleClient.predict_async`` in an open loop: one
sender thread sends each request when it is due, and latency runs from the
due time to completion, so a stalled sender counts.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import gc
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import flops, trace_reduce, traffic

WAIT_PAST_CLOSE_S = 60.0    # how long answers due in the window are awaited
SAMPLE_ROWS = 64            # rows compared with the reference, at least
REF_BLOCK = 8               # reference batch
WARM_IDS = 1 << 40          # token streams of warm-up requests start here
WARM_LINGER_S = 2.0         # batcher linger while the packed sequence is sent


class NoChip(RuntimeError):
    """JAX finds no TPU, too few chips, or a chip of unknown kind."""


class CompileLog:
    """Backend compiles and persistent-cache traffic, from JAX's own
    monitoring events."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s = 0, 0.0
        self.hits, self.misses, self.retrieve_s = 0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.retrieve_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "hits": self.hits, "misses": self.misses,
                "retrieve_s": self.retrieve_s}


def chips_for(n: int) -> list:
    """The first ``n`` TPU chips, or :class:`NoChip`.  A chip kind missing
    from the peak table is an error too."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (devices: {devs})")
    try:
        flops.peaks(devs[0].device_kind)
    except ValueError as e:
        raise NoChip(str(e)) from None
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def base_key(seed: int):
    """A PRNG key from any whole-number seed, 64-bit ones included."""
    import jax
    a, b = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(a) >> 1), int(b) >> 1)


_GENERATORS: dict = {}


def make_weights(ref, cfg: dict, seed: int, m: int, device, dtype):
    """Member ``m``'s weights, made in one jitted call on ``device``."""
    import jax
    from jax.sharding import SingleDeviceSharding
    sig = (ref.__name__, repr(ref.shapes(cfg)), str(dtype), device)
    fn = _GENERATORS.get(sig)
    if fn is None:
        fn = _GENERATORS[sig] = jax.jit(
            lambda k: ref.init_params(k, cfg, dtype),
            out_shardings=SingleDeviceSharding(device))
    return fn(jax.random.fold_in(base_key(seed), m))


def cells_for(chips: list) -> list:
    """Allocation cells: one per chip, as the serving launcher makes them."""
    from repro.core import tpu_cells
    return tpu_cells(chips, 1)


@dataclasses.dataclass
class Served:
    """The system under test and what set-up learned about it."""
    system: object
    client: object
    cfg: dict
    program_cfg: object
    chips: list
    members: int
    batch: int


def build(cfg: dict, ref, seq: int, seed: int, chips: list) -> Served:
    """Planner, weights, InferenceSystem and client."""
    import jax.numpy as jnp
    from repro.core import AllocationOptimizer, AnalyticBench
    from repro.serving.client import EnsembleClient
    from repro.serving.system import InferenceSystem

    pcfg = ref.program_config(cfg)
    members = int(cfg["members"])
    dtype = cfg["member_dtype"]
    cfgs, dts = [pcfg] * members, [dtype] * members
    cells = cells_for(chips)
    res = AllocationOptimizer(
        cfgs, cells, AnalyticBench(cfgs, seq=seq, member_dtypes=dts),
        max_iter=10, max_neighs=100, seq=seq, cache_path=None,
        member_dtypes=dts).optimize()
    alloc = res.matrix
    print("allocation matrix:\n" + alloc.pretty(), flush=True)
    store = jnp.bfloat16 if dtype == "bf16" else jnp.float32
    params = []
    for m in range(members):
        d = int(np.nonzero(alloc.A[:, m])[0][0])
        params.append(make_weights(ref, cfg, seed, m,
                                   cells[d].jax_devices[0], store))
    system = InferenceSystem(cfgs, params, alloc,
                             segment_size=int(cfg["segment_size"]),
                             max_seq=seq, combine=cfg["combine"],
                             supervise=True, member_dtypes=dts)
    del params
    batch = max(w.batch_size for w in system.workers)
    return Served(system, EnsembleClient(system), cfg, pcfg, list(chips),
                  members, batch)


def _options(high: bool):
    from repro.serving.segments import PredictOptions
    return PredictOptions(priority="high" if high else "normal")


def warm_up(served: Served, mix: traffic.Mix, seed: int) -> int:
    """Send, before the window, every shape the traffic can produce: each
    size alone at each priority (its row bucket, and the combine at its row
    count), then a sequence packed back to back that starts a request of
    every size at every row offset within a compiled batch -- every way the
    traffic can cut a request at batch boundaries.  The batchers' linger
    is lengthened while that sequence is sent, so that no partial batch
    flushes between two of its requests, and restored after.  Returns the
    requests sent."""
    client, system = served.client, served.system
    seq, vocab = mix.seq, served.program_cfg.vocab_size
    sent = 0
    for n in sorted(set(mix.rows)):
        for high in sorted({False, mix.high_share > 0}):
            client.predict(traffic.tokens(seed, WARM_IDS + sent, n, seq,
                                          vocab), _options(high), timeout=600)
            sent += 1
    order = traffic.warm_sequence(mix.rows, served.batch)
    lingers = [(w, w.linger_s) for w in system.workers]
    for w, _ in lingers:
        w.linger_s = WARM_LINGER_S
    try:
        handles = [client.predict_async(
            traffic.tokens(seed, WARM_IDS + sent + k, n, seq, vocab),
            _options(False)) for k, n in enumerate(order)]
        system.quiesce()
        for h in handles:
            h.result(600)
    finally:
        for w, linger in lingers:
            w.linger_s = linger
    return sent + len(order)


@dataclasses.dataclass
class Record:
    i: int
    rows: int
    high: bool
    due: float = 0.0          # perf_counter the request was due
    sent: float = 0.0         # perf_counter predict_async was called
    submitted: float = 0.0    # perf_counter predict_async returned
    done: Optional[float] = None
    error: Optional[str] = None
    y: Optional[np.ndarray] = None


def _annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(trace_reduce.SPAN_PREFIX + name)


def _finish(rec: Record, handle, deadline: float) -> None:
    try:
        y = handle.result(max(0.0, deadline - time.perf_counter()))
        rec.done = time.perf_counter()
        if handle.quality() < 1.0:
            rec.error = f"degraded answer, quality {handle.quality()}"
        else:
            rec.y = y
    except Exception as e:              # a refused or failed request
        rec.done = time.perf_counter()
        rec.error = f"{type(e).__name__}: {e}"


def open_loop(served: Served, reqs: List[traffic.Req], xs: Dict[int, object],
              t0: float, seconds: float) -> List[Record]:
    recs = [Record(r.i, r.rows, r.high, due=t0 + r.due) for r in reqs]
    deadline = t0 + seconds + WAIT_PAST_CLOSE_S
    with concurrent.futures.ThreadPoolExecutor(32) as pool:
        for r, rec in zip(reqs, recs):
            now = time.perf_counter()
            if rec.due > now:
                with _annotate("arrival_wait"):
                    time.sleep(rec.due - now)
            rec.sent = time.perf_counter()
            try:
                with _annotate("submit"):
                    h = served.client.predict_async(xs[r.i], _options(r.high))
            except Exception as e:
                rec.submitted = rec.done = time.perf_counter()
                rec.error = f"{type(e).__name__}: {e}"
                continue
            rec.submitted = time.perf_counter()
            pool.submit(_finish, rec, h, deadline)
    return recs


@dataclasses.dataclass
class Window:
    """What the measured window saw, handed to every metric reader."""
    seconds: float
    t0: float
    records: List[Record]
    counters0: dict
    counters1: dict
    stages0: dict
    stages1: dict
    compiles0: dict
    compiles1: dict
    mix: traffic.Mix
    chips: int
    members: int
    cfg: dict
    setup_s: float = 0.0
    setup_compiles: Optional[dict] = None
    peak: Optional[dict] = None
    trace: Optional[dict] = None

    @property
    def close(self) -> float:
        return self.t0 + self.seconds

    def completed_in_window(self) -> List[Record]:
        return [r for r in self.records if r.error is None
                and r.done is not None and r.done <= self.close]

    def latencies_ms(self) -> List[float]:
        """Due-to-answer latency of every answered request due in the
        window."""
        return [1e3 * (r.done - r.due) for r in self.records
                if r.error is None and r.done is not None]

    def counter(self, name: str) -> float:
        return self.counters1.get(name, 0.0) - self.counters0.get(name, 0.0)

    def stage(self, name: str):
        """(seconds, count) a program stage timer added over the window."""
        a, b = self.stages0.get(name, {}), self.stages1.get(name, {})
        return (b.get("total_s", 0.0) - a.get("total_s", 0.0),
                b.get("count", 0) - a.get("count", 0))


def measure(served: Served, mix: traffic.Mix, seed: int, seconds: float,
            log: CompileLog, trace_dir: Optional[str] = None) -> Window:
    """The measured window, with the profiler on over all of it when
    ``trace_dir`` is given (started before the window opens, the traced
    span being the window itself).  Program counters and compile counts are
    read when the window opens and when it closes."""
    import jax
    system = served.system
    vocab = served.program_cfg.vocab_size
    reqs = traffic.open_schedule(mix, seconds)
    xs = {r.i: traffic.tokens(seed, r.i, r.rows, mix.seq, vocab) for r in reqs}
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        t0 = time.perf_counter() + 0.05
        ends = []

        def at_close():
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
            ends.append((system.serving_counters(), system.stage_timings(),
                         log.snapshot()))

        side = [threading.Thread(target=at_close, name="close")]
        if trace_dir is not None:
            side.append(threading.Thread(target=_traced_span, name="traced",
                                         args=(t0, t0 + seconds)))
        start = (system.serving_counters(), system.stage_timings(),
                 log.snapshot())
        for t in side:
            t.start()
        with _annotate("window"):
            recs = open_loop(served, reqs, xs, t0, seconds)
        for t in side:
            t.join()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    (c0, s0, k0), (c1, s1, k1) = start, ends[0]
    return Window(seconds, t0, recs, c0, c1, s0, s1, k0, k1, mix,
                  len(served.chips), served.members, served.cfg)


def _traced_span(start: float, stop: float) -> None:
    """The span that marks the window in the profiler's trace."""
    time.sleep(max(0.0, start - time.perf_counter()))
    with _annotate("traced"):
        time.sleep(max(0.0, stop - time.perf_counter()))


def memory_peak(chips) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in chips)


def sample(window: Window, seed: int) -> List[Record]:
    """Answers compared with the reference: one of the largest requests,
    then others drawn from the seed, until :data:`SAMPLE_ROWS` rows."""
    done = [r for r in window.records if r.error is None and r.y is not None]
    if not done:
        return []
    rng = np.random.default_rng([seed, 0x5A3])
    big = max(r.rows for r in done)
    first = [r for r in done if r.rows == big]
    picked = [first[int(rng.integers(len(first)))]]
    rest = [r for r in done if r is not picked[0]]
    for k in rng.permutation(len(rest)):
        if sum(r.rows for r in picked) >= SAMPLE_ROWS:
            break
        picked.append(rest[int(k)])
    return sorted(picked, key=lambda r: r.i)


def reference_scores(ref, cfg: dict, seed: int, chips: list, xs: np.ndarray,
                     weights: str = "stored") -> np.ndarray:
    """The ensemble's answer for rows ``xs`` by the plain reference: the
    mean of each member's last-position logits, member ``m`` on chip
    ``m % chips``, its weights drawn again from the seed.  ``weights="int8"``
    gives the correctness control."""
    import jax
    import jax.numpy as jnp
    members = int(cfg["members"])
    n = len(xs)
    pad = (-n) % REF_BLOCK
    blocks = np.concatenate([xs, np.zeros((pad, xs.shape[1]), xs.dtype)])
    y = np.zeros((len(blocks), cfg["vocab_size"]), np.float64)
    for m in range(members):
        chip = chips[m % len(chips)]
        params = make_weights(ref, cfg, seed, m, chip, jnp.bfloat16 if
                              cfg["member_dtype"] == "bf16" else jnp.float32)
        for b in range(0, len(blocks), REF_BLOCK):
            x = jax.device_put(blocks[b:b + REF_BLOCK], chip)
            y[b:b + REF_BLOCK] += np.asarray(
                ref.last_logits(params, x, cfg, weights=weights), np.float64)
        del params
    return (y / members)[:n]


def compare(y: np.ndarray, r: np.ndarray) -> Dict[str, float]:
    """The numbers ``correct`` is decided on."""
    diff = y.astype(np.float64) - r
    return {"max_abs_err": float(np.abs(diff).max()),
            "rms_rel_err": float(np.sqrt(np.mean(diff ** 2))
                                 / np.sqrt(np.mean(r ** 2)))}


def release(served: Served) -> None:
    served.system.shutdown()
    served.system = served.client = None
    gc.collect()
