"""Coalescing-scheduler tests (ISSUE 2): cross-request batch packing,
ensemble selection (``predict(members=...)``) under coalesced batches,
every combine rule against the reference, deterministic flush counts,
row-count (not
message-count) accounting in the combiner and accumulator, the quiesce
flush, mismatched-seq buffer pooling, and best-fit input-buffer reuse."""
import queue
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.models as M
from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.serving.accumulator import PredictionAccumulator
from repro.serving.combiner import DeviceCombiner
from repro.serving.segments import Message, Request
from repro.serving.system import InferenceSystem
from repro.serving.worker import ALT_POOL_CAP

SEQ = 16


@pytest.fixture(scope="module")
def ens2():
    cfgs = ensemble("ENS4")[:2]
    rng = jax.random.PRNGKey(0)
    params = [M.init_params(jax.random.fold_in(rng, i), c)
              for i, c in enumerate(cfgs)]
    return cfgs, params


def oracle(cfgs, params, X, weights=None):
    w = weights if weights is not None else [1 / len(cfgs)] * len(cfgs)
    out = np.zeros((X.shape[0], cfgs[0].vocab_size), np.float32)
    for i, (c, p) in enumerate(zip(cfgs, params)):
        fe = jnp.zeros((X.shape[0], c.frontend_tokens, c.fdim)) \
            if c.frontend_tokens else None
        lg, _ = M.forward(p, c, jnp.asarray(X), fe)
        out += np.asarray(lg[:, -1, :c.vocab_size]) * w[i]
    return out


def make_system(cfgs, params, A, **kw):
    devs = host_cpus(A.shape[0], memory_bytes=8 * 1024 ** 3)
    alloc = AllocationMatrix(devs, [c.name for c in cfgs], A)
    return InferenceSystem(cfgs, params, alloc, max_seq=SEQ, **kw)


def small_batch(rng, k, sizes=(3, 5, 6, 9, 12)):
    return [rng.integers(0, 512, (sizes[i % len(sizes)], SEQ)).astype(np.int32)
            for i in range(k)]


# ---- ensemble selection under coalesced batches ------------------------------

def test_members_subsets_interleaved_under_coalescing(ens2):
    """predict(members=...) stays correct when rows from requests with
    DIFFERENT member subsets coalesce into shared batches; subset weights
    renormalize per request."""
    cfgs, params = ens2
    w = np.array([0.75, 0.25], np.float32)
    Xs = small_batch(np.random.default_rng(10), 12)
    member_sets = [[0], [1], [0, 1]]
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=32,
                     combine="weighted", weights=w,
                     max_in_flight=12) as s:
        handles = [s.predict_async(x, members=member_sets[i % 3])
                   for i, x in enumerate(Xs)]
        Ys = [h.result(120.0) for h in handles]
    for i, (x, y) in enumerate(zip(Xs, Ys)):
        ms = member_sets[i % 3]
        sub_w = w[ms] / w[ms].sum()
        ref = oracle([cfgs[m] for m in ms], [params[m] for m in ms], x, sub_w)
        np.testing.assert_allclose(y, ref, atol=2e-5)


def vote_oracle(cfgs, params, X):
    """Each member votes for its argmax class with weight 1/members."""
    out = np.zeros((X.shape[0], cfgs[0].vocab_size), np.float32)
    for c, p in zip(cfgs, params):
        top = oracle([c], [p], X).argmax(axis=1)
        out[np.arange(X.shape[0]), top] += 1.0 / len(cfgs)
    return out


@pytest.mark.parametrize("combine", ["mean", "vote", "pallas"])
def test_device_combine_parity_under_coalescing(ens2, combine):
    """Acceptance: the device combine under coalescing matches the
    reference of each request's member subset, for interleaved small
    requests whose rows share batches."""
    cfgs, params = ens2
    Xs = small_batch(np.random.default_rng(11), 10)
    member_sets = [[0, 1], [1], [0]]
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=32,
                     combine=combine, max_in_flight=10) as s:
        handles = [s.predict_async(x, members=member_sets[i % 3])
                   for i, x in enumerate(Xs)]
        Ys = [h.result(120.0) for h in handles]
    ref_fn = vote_oracle if combine == "vote" else oracle
    for i, (x, y) in enumerate(zip(Xs, Ys)):
        ms = member_sets[i % 3]
        ref = ref_fn([cfgs[m] for m in ms], [params[m] for m in ms], x)
        np.testing.assert_allclose(y, ref, atol=2e-5)


def test_deterministic_flush_counts_under_coalescing(ens2):
    """Whatever way spans pack into batches, each (request, segment) posts
    exactly one device partial per device: message counts stay
    devices x segments."""
    cfgs, params = ens2
    Xs = small_batch(np.random.default_rng(12), 9, sizes=(5, 20, 40))
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     max_in_flight=9) as s:
        before = s.accumulator.data_messages
        posted0 = sum(c.partials_posted for c in s.combiners.values())
        handles = [s.predict_async(x) for x in Xs]
        for h in handles:
            h.result(120.0)
        n_segments = sum(-(-x.shape[0] // 16) for x in Xs)
        assert s.accumulator.data_messages - before == n_segments
        posted = sum(c.partials_posted for c in s.combiners.values()) - posted0
        assert posted == n_segments


def test_single_segment_requests_spread_across_instances(ens2):
    """Striping rotates by request id, so a stream of single-segment (small)
    requests spreads across a model's data-parallel instances instead of
    pinning every request to the s=0 instance."""
    cfgs, params = ens2
    A = np.array([[8, 8],
                  [8, 0]])                  # model 0 data-parallel on d0+d1
    with make_system(cfgs, params, A, segment_size=16, fake=True,
                     max_in_flight=8) as s:
        handles = [s.predict_async(np.zeros((5, SEQ), np.int32))
                   for _ in range(8)]
        for h in handles:
            h.result(60.0)
        # d1 hosts only model 0's second instance: it must have seen work
        assert s.combiners[1].partials_posted > 0
        assert s.combiners[0].partials_posted > 0


# ---- row-count accounting (combiner / accumulator units) ---------------------

def _mk_request(n, num_classes=8, segment_size=16, members=(0, 1),
                weights=(0.6, 0.4)):
    return Request(0, np.zeros((n, SEQ), np.int32), n, num_classes,
                   segment_size, list(members),
                   {m: w for m, w in zip(members, weights)}, "weighted")


@pytest.mark.parametrize("to_device", [False, True])
def test_combiner_counts_rows_not_messages(to_device):
    """A member's segment arriving split across row-ranges still flushes
    exactly once, when members x segment_rows rows have been folded."""
    req = _mk_request(12)
    rng = np.random.default_rng(0)
    P0 = rng.normal(size=(12, 8)).astype(np.float32)
    P1 = rng.normal(size=(12, 8)).astype(np.float32)
    conv = (lambda a: jnp.asarray(a)) if to_device else (lambda a: a)
    q = queue.Queue()
    comb = DeviceCombiner("d0", q)
    comb.begin(req, {0: 2})
    # member 0, split rows
    comb.commit(req, 0, comb.add(req, 0, 0, conv(P0[:5]), row_lo=0))
    assert q.empty() and comb.partials_posted == 0
    # member 1, whole seg
    comb.commit(req, 0, comb.add(req, 0, 1, conv(P1), row_lo=0))
    assert q.empty()                                  # rows: 5 + 12 of 24
    # member 0, tail rows
    comb.commit(req, 0, comb.add(req, 0, 0, conv(P0[5:]), row_lo=5))
    msg = q.get_nowait()
    assert comb.partials_posted == 1 and msg.count == 2 and msg.m is None
    np.testing.assert_allclose(msg.P, 0.6 * P0 + 0.4 * P1, atol=1e-5)
    assert not comb._parts and not comb._expected     # state fully retired


def test_combiner_pallas_rule_row_spans():
    """The accumulate-into-partial Pallas kernel fold stays correct when a
    member's contribution arrives as row spans of the segment."""
    req = _mk_request(12, num_classes=16)
    req.combine = "pallas"
    rng = np.random.default_rng(1)
    P0 = rng.normal(size=(12, 16)).astype(np.float32)
    P1 = rng.normal(size=(12, 16)).astype(np.float32)
    q = queue.Queue()
    comb = DeviceCombiner("d0", q)
    comb.begin(req, {0: 2})
    comb.commit(req, 0, comb.add(req, 0, 0, jnp.asarray(P0[:7]), row_lo=0))
    comb.commit(req, 0, comb.add(req, 0, 0, jnp.asarray(P0[7:]), row_lo=7))
    comb.commit(req, 0, comb.add(req, 0, 1, jnp.asarray(P1), row_lo=0))
    msg = q.get_nowait()
    np.testing.assert_allclose(msg.P, 0.6 * P0 + 0.4 * P1, atol=1e-5)


def test_accumulator_counts_rows_not_messages():
    """A request owes n x members member-rows; each partial debits count x
    segment rows.  Segment 0 closes with one count=1 partial from each of
    two devices, segment 1 with one count=2 partial from one device: the
    handle completes only once both devices have posted segment 0."""
    req = _mk_request(20, weights=(0.5, 0.5))
    rng = np.random.default_rng(2)
    d0, d1, d01 = (rng.normal(size=(16, 8)).astype(np.float32),
                   rng.normal(size=(16, 8)).astype(np.float32),
                   rng.normal(size=(4, 8)).astype(np.float32))
    q = queue.Queue()
    acc = PredictionAccumulator(q, 2, combine="weighted",
                                weights=np.array([0.5, 0.5], np.float32))
    acc.start()
    try:
        handle = acc.begin(req)
        assert handle.remaining == 40                  # rows, not messages
        q.put(Message(1, None, d01, rid=0, count=2))
        q.put(Message(0, None, d0, rid=0, count=1))
        deadline = time.perf_counter() + 10.0
        while handle.remaining != 16 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert handle.remaining == 16                  # device 1 still owes
        assert not handle.done.is_set()
        q.put(Message(0, None, d1, rid=0, count=1))
        Y = handle.result(30.0)
        np.testing.assert_allclose(Y[:16], d0 + d1, atol=1e-6)
        np.testing.assert_array_equal(Y[16:], d01)
        assert handle.messages == 3
    finally:
        acc.stop()


def test_accumulator_device_partial_debits_count_times_rows():
    req = _mk_request(10, weights=(0.5, 0.5))
    q = queue.Queue()
    acc = PredictionAccumulator(q, 2)
    acc.start()
    try:
        handle = acc.begin(req)
        partial = np.full((10, 8), 2.0, np.float32)
        q.put(Message(0, None, partial, rid=0, count=2))
        Y = handle.result(30.0)
        np.testing.assert_allclose(Y, partial)
    finally:
        acc.stop()


# ---- linger / quiesce --------------------------------------------------------

def test_quiesce_flushes_lingering_partial_batch(ens2):
    """With an effectively-infinite linger a lone small request sits in an
    open batch; quiesce() force-flushes it."""
    cfgs, params = ens2
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     fake=True, max_wait_us=30_000_000) as s:
        h = s.predict_async(np.zeros((3, SEQ), np.int32))
        time.sleep(0.3)
        assert not h.done.is_set()          # batch is lingering open
        s.quiesce()
        assert np.all(h.result(30.0) == 0)


def test_bounded_linger_flushes_without_quiesce(ens2):
    """The default linger bounds single-request latency: a partial batch
    flushes on its own once max_wait_us expires."""
    cfgs, params = ens2
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     fake=True, max_wait_us=1000) as s:
        t0 = time.perf_counter()
        s.predict(np.zeros((3, SEQ), np.int32), timeout=30.0)
        assert time.perf_counter() - t0 < 5.0


# ---- buffer pooling ----------------------------------------------------------

def test_mismatched_seq_buffers_are_pooled(ens2):
    """Requests whose seq width differs from the compiled ring draw batcher
    buffers from a bounded per-width pool instead of allocating per slot."""
    cfgs, params = ens2
    alt_seq = SEQ // 2
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     fake=True) as s:
        for _ in range(6):
            Y = s.predict(np.zeros((20, alt_seq), np.int32), timeout=30.0)
            assert Y.shape == (20, cfgs[0].vocab_size)
        for w in s.workers:
            pools = w._alt_pool
            assert alt_seq in pools and len(pools[alt_seq]) >= 1
            assert all(len(p) <= ALT_POOL_CAP for p in pools.values())
            assert all(b.shape == (w._span, alt_seq)
                       for b in pools[alt_seq])


def test_take_buffer_best_fit(ens2):
    """_take_buffer picks the SMALLEST fitting pooled buffer, so one huge
    early request can't pin oversized buffers for every later request."""
    cfgs, params = ens2
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     fake=True) as s:
        big = np.zeros((512, SEQ), np.int32)
        mid = np.zeros((64, SEQ), np.int32)
        small = np.zeros((32, SEQ), np.int32)
        with s._pool_lock:
            s._buffer_pool[:] = [big, mid, small]
        got = s._take_buffer(40, SEQ)
        assert got is mid                   # best fit, not first fit (big)
        with s._pool_lock:
            assert any(b is big for b in s._buffer_pool)
            assert any(b is small for b in s._buffer_pool)


# ---- metrics -----------------------------------------------------------------

def test_padding_counters_and_queue_gauge(ens2):
    cfgs, params = ens2
    X = np.random.default_rng(13).integers(0, 512, (20, SEQ)).astype(np.int32)
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     fake=True) as s:
        s.predict(X, timeout=30.0)
        c = s.serving_counters()
        assert c["batches"] > 0 and c["spans"] > 0
        assert 0 < c["rows_valid"] <= c["rows_dispatched"]
        assert 0 < c["padding_efficiency"] <= 1.0
        g = s.serving_gauges()
        depth_keys = [k for k in g if k.startswith("queue_depth.")]
        assert depth_keys and all(g[k]["max"] >= 0 for k in depth_keys)
