"""The pipeline's stages on both sinks (DESIGN.md §6): the StageTimers
counts each stage makes per request, chunk, flush and posted partial, and
the ``serving.<stage>`` spans a ``jax.profiler`` session records."""
import glob
import os
import threading
import time

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


def make_system(cfgs, params, A=((8, 8),), **kw):
    A = np.array(A)
    devs = host_cpus(A.shape[0], memory_bytes=8 * 1024 ** 3)
    alloc = AllocationMatrix(devs, [c.name for c in cfgs], A)
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
    assert counts["combine"] == counters["folds_ahead"] == 2 * segments
    assert "fold_rebuilds" not in counters
    assert counts["accumulate"] == segments
    assert "transfer" not in counts and "batcher_wait" not in counts


def test_each_device_copies_its_partial_after_the_combine_wait(ens2):
    """With one member on each of two devices, each device's combiner posts
    one partial per segment: one ``copy`` per partial posted, each on the
    thread that waited for that partial's fold just before."""
    cfgs, params = ens2
    with make_system(cfgs, params, A=((8, 0), (0, 8))) as s:
        order, add = [], s.timers.add

        def add_in_order(stage, dt):
            order.append((threading.get_ident(), stage))
            add(stage, dt)

        s.timers.add = add_in_order
        _requests(s)
        counts = _counts(s)
        posted = [c.partials_posted for c in s.combiners.values()]
    segments = sum(-(-n // 8) for n in ROWS)
    assert posted == [segments, segments]
    assert counts["copy"] == counts["combine_wait"] == sum(posted)
    last = {}
    for tid, stage in order:
        if stage == "copy":
            assert last.get(tid) == "combine_wait"
        last[tid] = stage


def test_each_fold_is_made_by_the_predictor_before_its_chunk_is_waited_for(
        ens2):
    """A segment's fold is dispatched by its member's predictor right
    behind the step that completes it, so it starts before the sender's
    device wait for that step ends; the sender commits it after that
    wait.  One fold per (segment, member), none rebuilt."""
    cfgs, params = ens2
    with make_system(cfgs, params) as s:
        member = {w.worker_id: w.model_idx for w in s.workers}
        comb, = s.combiners.values()
        order, add = [], s.timers.add
        add_fold, commit = comb.add, comb.commit

        def add_in_order(stage, dt):
            order.append((threading.current_thread().name, stage,
                          time.perf_counter() - dt, time.perf_counter()))
            add(stage, dt)

        def fold(req, seg, m, P, row_lo=0):
            order.append((threading.current_thread().name, "fold",
                          (req.rid, seg, m), time.perf_counter()))
            return add_fold(req, seg, m, P, row_lo)

        def commit_in_order(req, seg, f):
            order.append((threading.current_thread().name, "commit",
                          (req.rid, seg), time.perf_counter()))
            commit(req, seg, f)

        s.timers.add = add_in_order
        comb.add, comb.commit = fold, commit_in_order
        _requests(s)
        counters = s.serving_counters()
    segments = sum(-(-n // 8) for n in ROWS)
    folds = {ev[2]: ev[3] for ev in order if ev[1] == "fold"}
    assert len(folds) == counters["folds_ahead"] == 2 * segments
    assert "fold_rebuilds" not in counters
    combines = [ev for ev in order if ev[1] == "combine"]
    assert len(combines) == 2 * segments
    assert all(ev[0].endswith("-predictor") for ev in combines)
    assert all(ev[0].endswith("-predictor")
               for ev in order if ev[1] == "fold")
    waited, last, commits = {}, {}, 0
    for thread, stage, a, b in order:
        if stage == "device_wait":
            waited[thread] = b            # this chunk's wait ended at b
        elif stage == "commit":
            assert thread.endswith("-sender")
            rid, seg = a
            m = member[thread[:-len("-sender")]]
            assert folds[(rid, seg, m)] < waited[thread]
            commits += 1
        elif stage == "copy":
            assert last.get(thread) == "combine_wait"
        last[thread] = stage
    assert commits == 2 * segments


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
