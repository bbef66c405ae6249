"""Chunk-granular predictor pipeline tests (ISSUE 5): bit-identical results
vs each request served alone, priority chunk ordering under a
saturated ring, refcount-correct slot recycling on CPU (aliased
``device_put``), quiesce/FLUSH barriers with chunks in the dispatch queue,
dropped-at-dequeue chunks of cancelled/expired requests, the deadline-aware
steal policy, and the per-class latency metrics."""
import queue
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.models as M
from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.serving.admission import AdmissionQueue, DispatchQueue, chunk_level
from repro.serving.segments import (ChunkDesc, DeadlineExceeded, FLUSH,
                                    PRIORITY_HIGH, PRIORITY_NORMAL,
                                    PredictOptions, Request, RequestCancelled,
                                    SlotRef, Span)
from repro.serving.system import InferenceSystem
from repro.serving.worker import RING_SLOTS, Worker

SEQ = 16


@pytest.fixture(scope="module")
def ens2():
    cfgs = ensemble("ENS4")[:2]
    rng = jax.random.PRNGKey(0)
    params = [M.init_params(jax.random.fold_in(rng, i), c)
              for i, c in enumerate(cfgs)]
    return cfgs, params


def make_system(cfgs, params, A, **kw):
    devs = host_cpus(A.shape[0], memory_bytes=8 * 1024 ** 3)
    alloc = AllocationMatrix(devs, [c.name for c in cfgs], A)
    return InferenceSystem(cfgs, params, alloc, max_seq=SEQ, **kw)


def _mk_request(n=16, priority=PRIORITY_NORMAL, deadline=None, rid=0):
    return Request(rid, np.zeros((n, SEQ), np.int32), n, 8, 16, [0],
                   {0: 1.0}, "mean", priority=priority, deadline=deadline)


# ---- unit: chunk level / dispatch queue / slot refcount ----------------------

def test_chunk_level_most_urgent_span_wins():
    hi = _mk_request(priority=PRIORITY_HIGH)
    lo = _mk_request(priority=PRIORITY_NORMAL)
    assert chunk_level([Span(lo, 0, 0, 0, 4)]) == PRIORITY_NORMAL
    assert chunk_level([Span(lo, 0, 0, 0, 4),
                        Span(hi, 0, 0, 4, 2)]) == PRIORITY_HIGH
    assert chunk_level([]) == PRIORITY_NORMAL


def test_dispatch_queue_high_chunks_jump_bulk():
    """High-priority chunks overtake queued bulk chunks, FIFO within a
    class; chunks are never stolen or migrated."""
    q = DispatchQueue()
    ref = SlotRef(None, np.zeros((8, SEQ), np.int32), 4)
    bulk = [ChunkDesc(ref, 0, 8, 8, [], PRIORITY_NORMAL) for _ in range(3)]
    hot = ChunkDesc(ref, 0, 8, 8, [], PRIORITY_HIGH)
    for c in bulk[:2]:
        q.put(c, c.level)
    q.put(hot, hot.level)
    q.put(bulk[2], bulk[2].level)
    order = [q.get_nowait() for _ in range(4)]
    assert order == [hot, bulk[0], bulk[1], bulk[2]]
    with pytest.raises(TypeError):
        q.steal(4)
    with pytest.raises(TypeError):
        q.drain_descriptors()


def test_slot_ref_release_exactly_once_owner():
    ref = SlotRef(2, np.zeros((8, SEQ), np.int32), 3)
    assert ref.pending == 3
    assert not ref.release()
    assert not ref.release()
    assert ref.release()          # the zero-crossing release owns recycling
    assert ref.pending == 0


# ---- bit-identical results under chunk reordering ----------------------------

@pytest.mark.parametrize("combine", ["mean", "pallas"])
def test_chunk_pipeline_bit_identical_vs_uncoalesced(ens2, combine):
    """Acceptance: ensemble outputs of interleaved requests, whose rows
    coalesce into shared slots, are bit-identical to the same system
    serving each request alone, in order — including member subsets and
    mixed priorities (which reorder chunks), for mean partials and for the
    Pallas accumulate kernel's.  Request sizes are multiples of the
    compiled batch so both schedules group the same rows into the same
    compiled shapes and the comparison is exact, not approximate."""
    cfgs, params = ens2
    rng = np.random.default_rng(7)
    sizes = [8, 16, 24, 8, 32, 16]
    member_sets = [[0, 1], [0], [1], [0, 1], [0], [0, 1]]
    Xs = [rng.integers(0, 512, (n, SEQ)).astype(np.int32) for n in sizes]

    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=32,
                     combine=combine, max_in_flight=6) as s:
        Y_ref = [s.predict(x, members=m, timeout=120.0)
                 for x, m in zip(Xs, member_sets)]
        opts = [PredictOptions(priority="high" if i % 2 else "normal")
                for i in range(len(Xs))]
        handles = [s.predict_async(x, members=m, options=o)
                   for x, m, o in zip(Xs, member_sets, opts)]
        Ys = [h.result(120.0) for h in handles]
    for y, y_ref in zip(Ys, Y_ref):
        np.testing.assert_array_equal(y, y_ref)


# ---- priority chunk ordering under a saturated ring --------------------------

def test_high_priority_chunk_jumps_saturated_ring(ens2):
    """With every ring slot flushed full of bulk chunks (simulated device
    time makes the backlog deterministic), a late high-priority request
    completes well before the bulk drains — its chunk jumped the queued
    bulk chunks instead of waiting for RING_SLOTS slots."""
    cfgs, params = ens2
    with make_system(cfgs[:1], params[:1], np.array([[8]]), segment_size=32,
                     fake=True, fake_delay_us=3000,
                     max_in_flight=16, max_wait_us=100,
                     dispatch_ahead=2) as s:   # shallow committed window
        bulk = [s.predict_async(np.zeros((32, SEQ), np.int32))
                for _ in range(8)]          # 8 slots x 4 chunks x 3ms
        time.sleep(0.02)                    # let the ring saturate
        t0 = time.perf_counter()
        s.predict(np.zeros((8, SEQ), np.int32),
                  options=PredictOptions(priority="high"), timeout=60.0)
        hp_lat = time.perf_counter() - t0
        done_bulk = sum(h.done.is_set() for h in bulk)
        for h in bulk:
            h.result(60.0)
        st = s.stage_timings()
    # the bulk backlog is ~8x4x3ms of simulated device time; the high
    # request's chunk waits only for the committed window (~2 chunks)
    assert done_bulk < len(bulk) // 2, (hp_lat, done_bulk)
    assert hp_lat < 0.05, hp_lat
    assert st["dispatch_wait.high"]["mean_ms"] < \
        st["dispatch_wait.normal"]["mean_ms"]


# ---- refcount-correct slot recycling -----------------------------------------

def test_ring_slots_all_recycle_after_completion(ens2):
    """Every ring slot returns to the free list once its LAST chunk's
    output is materialized — with real models on CPU (`device_put` may
    alias the slot buffer), so corruption would show up as wrong results in
    the bit-identity test above, and leaks show up here."""
    cfgs, params = ens2
    with make_system(cfgs[:1], params[:1], np.array([[8]]), segment_size=32,
                     max_in_flight=8) as s:
        handles = [s.predict_async(
            np.random.default_rng(i).integers(0, 512, (24, SEQ))
            .astype(np.int32)) for i in range(8)]
        for h in handles:
            h.result(120.0)
        deadline = time.perf_counter() + 10.0
        for w in s.workers:
            while w._free_slots.qsize() < RING_SLOTS:
                assert time.perf_counter() < deadline, "slot leaked"
                time.sleep(0.005)
            assert w._free_slots.qsize() == RING_SLOTS


# ---- quiesce barriers with chunks in the dispatch queue ----------------------

def test_quiesce_barrier_with_queued_chunks(ens2):
    """quiesce(wait=True) must ack only after flushed chunks have been
    dispatched, and must not deadlock while the dispatch queue is deep with
    slow simulated-device chunks; the system keeps serving afterwards."""
    cfgs, params = ens2
    with make_system(cfgs[:1], params[:1], np.array([[8]]), segment_size=32,
                     fake=True, fake_delay_us=2000,
                     max_in_flight=8, max_wait_us=30_000_000,
                     dispatch_ahead=2) as s:
        handles = [s.predict_async(np.zeros((32, SEQ), np.int32))
                   for _ in range(4)]
        h_tail = s.predict_async(np.zeros((3, SEQ), np.int32))  # lingering
        assert s.quiesce(wait=True, timeout=30.0)
        for h in handles + [h_tail]:
            np.testing.assert_array_equal(h.result(30.0), 0)
        # re-entrant: quiesce/submit cycles stay legal on the chunk pipeline
        h2 = s.predict_async(np.zeros((5, SEQ), np.int32))
        assert s.quiesce(wait=True, timeout=30.0)
        np.testing.assert_array_equal(h2.result(30.0), 0)


# ---- dropped-at-dequeue chunks (cancelled / expired requests) ----------------

def _stall_predictor(monkeypatch, worker_ids):
    release = threading.Event()
    orig = Worker._predictor

    def stalling(self):
        if self.worker_id in worker_ids:
            release.wait(60.0)
        return orig(self)

    monkeypatch.setattr(Worker, "_predictor", stalling)
    return release


def test_cancelled_request_chunks_dropped_at_dequeue(ens2, monkeypatch):
    """A cancelled request's already-flushed chunks are dropped when
    dequeued — rows land on the DROPPED accounting path (`rows_dropped`),
    no device dispatch happens for them, the ring slots still recycle, and
    the worker keeps serving."""
    cfgs, params = ens2
    release = _stall_predictor(monkeypatch, {"w0.0"})
    with make_system(cfgs[:1], params[:1], np.array([[8]]), segment_size=32,
                     fake=True, max_in_flight=8,
                     max_wait_us=100) as s:
        try:
            h = s.predict_async(np.zeros((32, SEQ), np.int32))
            deadline = time.perf_counter() + 10.0
            while s.workers[0].dispatch_backlog() == 0:   # chunks flushed
                assert time.perf_counter() < deadline
                time.sleep(0.002)
            assert h.cancel()
            with pytest.raises(RequestCancelled):
                h.result(10.0)
        finally:
            release.set()
        # the stalled predictor now drains the queue: chunks are skipped
        deadline = time.perf_counter() + 10.0
        while s.serving_counters().get("rows_dropped", 0) < 32:
            assert time.perf_counter() < deadline, s.serving_counters()
            time.sleep(0.005)
        assert s.serving_counters()["rows_dropped"] == 32
        np.testing.assert_array_equal(          # slot recycled; still serving
            s.predict(np.zeros((8, SEQ), np.int32), timeout=30.0), 0)
        for w in s.workers:
            deadline = time.perf_counter() + 10.0
            while w._free_slots.qsize() < RING_SLOTS:
                assert time.perf_counter() < deadline, "slot leaked"
                time.sleep(0.005)


def test_expired_request_chunks_dropped_at_dequeue(ens2, monkeypatch):
    """A request whose deadline expires after its rows were packed (chunks
    already in the dispatch queue) resolves with DeadlineExceeded via the
    dequeue-time DROPPED path instead of occupying device time."""
    cfgs, params = ens2
    release = _stall_predictor(monkeypatch, {"w0.0"})
    with make_system(cfgs[:1], params[:1], np.array([[8]]), segment_size=32,
                     fake=True, max_in_flight=8,
                     max_wait_us=100) as s:
        try:
            h = s.predict_async(np.zeros((32, SEQ), np.int32),
                                options=PredictOptions(deadline_ms=150.0))
            deadline = time.perf_counter() + 10.0
            while s.workers[0].dispatch_backlog() == 0:
                assert time.perf_counter() < deadline
                time.sleep(0.002)
            time.sleep(0.2)                  # let the deadline lapse
        finally:
            release.set()
        with pytest.raises(DeadlineExceeded):
            h.result(10.0)
        deadline = time.perf_counter() + 10.0
        while s.serving_counters().get("rows_dropped", 0) < 32:
            assert time.perf_counter() < deadline, s.serving_counters()
            time.sleep(0.005)


# ---- deadline-aware steal policy (ROADMAP item i) ----------------------------

def test_steal_prefers_tightest_deadline():
    """Within the stealable tail region, descriptors with the tightest
    remaining deadline budget are selected (and returned) first;
    deadline-less descriptors rank loosest, newest first; sentinels still
    fence the sweep."""
    now = time.perf_counter()
    loose = _mk_request(deadline=now + 10.0, rid=1)
    tight = _mk_request(deadline=now + 0.5, rid=2)
    mid = _mk_request(deadline=now + 2.0, rid=3)
    none = _mk_request(deadline=None, rid=4)
    q = AdmissionQueue()
    for req in (loose, none, tight, mid):
        q.put((req, 0))
    assert [r.rid for r, _ in q.steal(3)] == [2, 3, 1]    # tightest first
    assert q.get_nowait()[0].rid == 4                     # loosest stays
    # sentinels fence the stealable region even for tight deadlines
    q2 = AdmissionQueue()
    q2.put((tight, 0))
    q2.put(FLUSH)
    q2.put((loose, 1))
    assert [r.rid for r, _ in q2.steal(8)] == [1]
    # no deadlines at all: classic newest-first tail steal, order preserved
    q3 = AdmissionQueue()
    items = [(_mk_request(rid=i), 0) for i in range(5)]
    for it in items:
        q3.put(it)
    assert q3.steal(2) == items[3:]


# ---- per-class latency metrics ----------------------------------------------

def test_latency_snapshot_and_hp_gauge(ens2):
    cfgs, params = ens2
    with make_system(cfgs[:1], params[:1], np.array([[8]]), segment_size=16,
                     fake=True, max_wait_us=100) as s:
        for i in range(4):
            s.predict(np.zeros((4, SEQ), np.int32), timeout=30.0,
                      options=PredictOptions(
                          priority="high" if i % 2 else "normal"))
        lat = s.latency_snapshot()
        assert set(lat) == {"high", "normal"}
        for cls in lat:
            assert lat[cls]["n"] == 2
            assert 0 < lat[cls]["p50_ms"] <= lat[cls]["p99_ms"]
        assert s.serving_gauges()["hp_p50_ms"]["last"] > 0
