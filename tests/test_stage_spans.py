"""The pipeline's stages on both sinks (DESIGN.md §6): the StageTimers
counts each stage makes per request, chunk, flush and posted partial, and
the ``serving.<stage>`` spans a ``jax.profiler`` session records."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.models as M
from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.serving import tracing
from repro.serving.metrics import StageTimers
from repro.serving.segments import PredictOptions
from repro.serving.system import InferenceSystem

SEQ = 16
ROWS = (1, 3, 8, 5, 16, 2)

STAGES = {"submit", "inflight_wait", "slot_wait", "linger", "predict",
          "device_wait", "copy", "combine", "combine_wait", "accumulate"}


@pytest.fixture(scope="module")
def ens2():
    cfgs = ensemble("ENS4")[:2]
    rng = jax.random.PRNGKey(0)
    params = [M.init_params(jax.random.fold_in(rng, i), c)
              for i, c in enumerate(cfgs)]
    return cfgs, params


def make_system(cfgs, params, **kw):
    devs = host_cpus(1, memory_bytes=8 * 1024 ** 3)
    alloc = AllocationMatrix(devs, [c.name for c in cfgs],
                             np.array([[8, 8]]))
    kw.setdefault("max_seq", SEQ)
    kw.setdefault("segment_size", 8)
    return InferenceSystem(cfgs, params, alloc, **kw)


def _requests(s, rows=ROWS):
    rng = np.random.default_rng(3)
    handles = [s.predict_async(rng.integers(0, 64, (n, SEQ)).astype(np.int32),
                               options=PredictOptions(priority=pri))
               for n, pri in zip(rows, ["normal", "high"] * len(rows))]
    for h in handles:
        h.result(60.0)


def _counts(s):
    return {k: v["count"] for k, v in s.stage_timings().items()}


def test_stage_counts_per_request_flush_chunk_and_partial(ens2):
    cfgs, params = ens2
    with make_system(cfgs, params, fake=True, tracing=True) as s:
        _requests(s)
        counts, counters = _counts(s), s.serving_counters()
        flushes = sum(1 for tid, evs in s.tracer.tracks().items()
                      if tid.endswith("/batcher")
                      for ev in evs if ev[1] == "pack")
        groups = sum(1 for tid, evs in s.tracer.tracks().items()
                     if tid.endswith("/sender")
                     for ev in evs if ev[1] == "transfer")
        posted = sum(c.partials_posted for c in s.combiners.values())
    segments = sum(-(-n // 8) for n in ROWS)
    assert counts["submit"] == counts["inflight_wait"] == len(ROWS)
    assert counts["linger"] == flushes > 0
    # normal-priority batches open on the ring and may wait for a slot;
    # high-priority ones never do
    assert 0 < counts["slot_wait"] < counts["linger"]
    # one hand-off to the sender per dispatched group
    assert counts["send_wait"] == groups > 0
    # no chunk was skipped, so every compiled batch was waited for once
    assert counts["device_wait"] == counters["batches"]
    # one posted partial per segment on the one device, each waited for
    # and copied once; one fold per (segment, member)
    assert counts["combine_wait"] == counts["copy"] == posted == segments
    assert counts["combine"] == 2 * segments
    assert counts["accumulate"] == segments
    assert "transfer" not in counts and "batcher_wait" not in counts


def test_host_combine_copies_each_chunk_after_its_device_wait(ens2):
    cfgs, params = ens2
    with make_system(cfgs, params, device_combine=False) as s:
        _requests(s)
        counts, counters = _counts(s), s.serving_counters()
    assert counts["device_wait"] == counts["copy"] == counters["batches"]
    assert "combine" not in counts and "combine_wait" not in counts


def test_no_profiler_session_times_the_stage_and_opens_no_span():
    assert not tracing.TraceAnnotation.is_enabled()
    assert tracing.open_span("linger") is None
    tracing.close_span(None)
    timers = StageTimers()
    with timers.stage("copy") as st:
        pass
    linger = timers.stage("linger").start()
    assert linger.stop() == linger.t1 >= linger.t0
    assert timers.count["copy"] == timers.count["linger"] == 1
    assert timers.total_s["copy"] == st.t1 - st.t0 >= 0


def _program_spans(log_dir):
    """``serving.*`` host events of the trace: [name, start_ns, dur_ns]."""
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return [[e.name, e.start_ns, e.duration_ns]
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(tracing.SPAN_PREFIX)]


def test_a_profiler_session_records_every_stage_span(ens2, tmp_path):
    cfgs, params = ens2
    with make_system(cfgs, params) as s:
        _requests(s, ROWS[:2])            # compile outside the session
        before = _counts(s)
        jax.profiler.start_trace(str(tmp_path))
        try:
            _requests(s)
            after = _counts(s)
        finally:
            jax.profiler.stop_trace()
    spans = _program_spans(str(tmp_path))
    by = {}
    for name, start, dur in spans:
        by.setdefault(name, []).append((start, start + dur))
    assert set(by) == {tracing.SPAN_PREFIX + st for st in STAGES}
    # both sinks of a stage count the same units
    for st in STAGES:
        assert len(by[tracing.SPAN_PREFIX + st]) == \
            after[st] - before.get(st, 0), st
    assert len(by["serving.submit"]) == len(by["serving.inflight_wait"]) \
        == len(ROWS)
    # the wait for an in-flight slot lies inside its request's submit span
    for a, b in by["serving.inflight_wait"]:
        assert any(s0 <= a and b <= s1 for s0, s1 in by["serving.submit"])
