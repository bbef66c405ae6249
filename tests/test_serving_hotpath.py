"""Hot-path tests (ISSUE 1): combine rules under member subsets, shape-bucket
batching round-trips, device-partial message reduction, multi-request
pipelining, and the versioned input-buffer swap that replaced the shared_x
reallocation race."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.models as M
from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.serving.system import InferenceSystem
from repro.serving.worker import bucket_for, bucket_ladder

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


# ---- shape buckets ----------------------------------------------------------

@pytest.mark.parametrize("n, batch, bucket", [
    (1, 8, 1), (2, 8, 2), (3, 8, 4), (5, 8, 8), (8, 8, 8),
    (9, 16, 16), (5, 64, 8), (17, 64, 32), (33, 64, 64), (64, 64, 64),
    (100, 64, 64),                        # clamped to the compiled batch
    (10, 12, 12),                         # a batch that is no power of two
])
def test_bucket_for_shapes(n, batch, bucket):
    """Next power of two >= n, clamped to [1, batch]: no floor above one
    row, so a 1-row chunk runs a 1-row program."""
    assert bucket_for(n, batch) == bucket


def test_worker_warms_every_bucket(ens2):
    """Each worker's constructor compiles its whole ladder (1, 2, 4, ...,
    batch), so no request compiles a predict program."""
    cfgs, params = ens2
    with make_system(cfgs, params, np.array([[8, 16]]),
                     segment_size=16) as s:
        for w in s.workers:
            ladder = bucket_ladder(w.batch_size)
            assert ladder == [2 ** k for k in
                              range(int(np.log2(w.batch_size)) + 1)]
            assert w.predict_fn._cache_size() == len(ladder)


@pytest.mark.parametrize("n, bucket", [(1, 1), (3, 4)])
def test_small_chunk_runs_its_own_bucket(ens2, n, bucket):
    """A lone n-row request runs the n-row bucket, not a padded batch of 8,
    and its rows equal the same rows computed inside a full batch.  Serving
    hits the programs the constructor compiled: the cache does not grow."""
    cfgs, params = ens2
    X = np.random.default_rng(20 + n).integers(0, 512, (8, SEQ)) \
        .astype(np.int32)
    with make_system(cfgs, params, np.array([[8, 8]]),
                     segment_size=16) as s:
        s.timers.reset()
        y_small = s.predict(X[:n])
        c = s.serving_counters()
        assert c[f"chunks.b{bucket}"] == len(cfgs)     # one chunk a member
        assert not any(c.get(f"chunks.b{b}") for b in (1, 2, 4, 8)
                       if b != bucket)
        assert c["rows_valid"] == len(cfgs) * n
        assert c["rows_dispatched"] == len(cfgs) * bucket
        y_full = s.predict(X)
        for w in s.workers:
            assert w.predict_fn._cache_size() == 4      # 1, 2, 4, 8
    np.testing.assert_allclose(y_small, y_full[:n], rtol=1e-5, atol=2e-5)
    np.testing.assert_allclose(y_small, oracle(cfgs, params, X[:n]),
                               atol=2e-5)


@pytest.mark.parametrize("n", [1, 7, 8, 9, 20, 31, 32, 70])
def test_batcher_padding_roundtrip(ens2, n):
    """Every request size survives the ring fill / bucket pad / unpad path:
    predictions equal the oracle regardless of how segments chunk."""
    cfgs, params = ens2
    X = np.random.default_rng(n).integers(0, 512, (n, SEQ)).astype(np.int32)
    with make_system(cfgs, params, np.array([[8, 16]]), segment_size=32) as s:
        Y = s.predict(X)
    assert Y.shape == (n, cfgs[0].vocab_size)
    np.testing.assert_allclose(Y, oracle(cfgs, params, X), atol=2e-5)


# ---- combine rules under member subsets ------------------------------------

def test_weighted_combine_member_subset(ens2):
    cfgs, params = ens2
    X = np.random.default_rng(2).integers(0, 512, (20, SEQ)).astype(np.int32)
    w = np.array([0.8, 0.2], np.float32)
    with make_system(cfgs, params, np.array([[8, 8]]), combine="weighted",
                     weights=w, segment_size=16) as s:
        y0 = s.predict(X, members=[0])        # weights renormalize to 1.0
        y1 = s.predict(X, members=[1])
    np.testing.assert_allclose(y0, oracle(cfgs[:1], params[:1], X), atol=2e-5)
    np.testing.assert_allclose(y1, oracle(cfgs[1:], params[1:], X), atol=2e-5)


# one device folds both members into one partial; two devices each fold
# one member, and the accumulator adds the two combiners' partials
PLACEMENTS = {"one_device": [[8, 8]], "two_devices": [[8, 0], [0, 8]]}


@pytest.mark.parametrize("placement", PLACEMENTS)
def test_vote_combine_member_subset(ens2, placement):
    cfgs, params = ens2
    A = np.array(PLACEMENTS[placement])
    X = np.random.default_rng(3).integers(0, 512, (20, SEQ)).astype(np.int32)
    with make_system(cfgs, params, A, combine="vote",
                     segment_size=16) as s:
        y_all = s.predict(X)
        y_sub = s.predict(X, members=[0])
        assert len(s.combiners) == A.shape[0]
        assert all(c.partials_posted for c in s.combiners.values())
    np.testing.assert_allclose(y_all.sum(axis=1), 1.0, atol=1e-6)
    # single-member vote: exactly one class gets weight 1.0 per row
    np.testing.assert_allclose(y_sub.max(axis=1), 1.0, atol=1e-6)
    np.testing.assert_allclose(y_sub.sum(axis=1), 1.0, atol=1e-6)
    np.testing.assert_array_equal(
        y_sub.argmax(axis=1),
        oracle(cfgs[:1], params[:1], X).argmax(axis=1))


@pytest.mark.parametrize("placement", PLACEMENTS)
@pytest.mark.parametrize("n", [37, 40])       # 37: non-block-aligned segments
def test_pallas_combine_non_aligned(ens2, placement, n):
    cfgs, params = ens2
    A = np.array(PLACEMENTS[placement])
    X = np.random.default_rng(4).integers(0, 512, (n, SEQ)).astype(np.int32)
    with make_system(cfgs, params, A, segment_size=16) as s:
        Y_mean = s.predict(X)
    with make_system(cfgs, params, A, combine="pallas",
                     segment_size=16) as s:
        Y_pallas = s.predict(X)
        Y_sub = s.predict(X, members=[1])
    np.testing.assert_allclose(Y_mean, Y_pallas, atol=1e-5)
    np.testing.assert_allclose(Y_sub, oracle(cfgs[1:], params[1:], X),
                               atol=2e-5)


# ---- device-resident partial combine ---------------------------------------

def test_partial_combine_message_reduction(ens2):
    """Co-located workers post one partial per device per segment: messages
    drop from M x segments to devices x segments."""
    cfgs, params = ens2
    X = np.random.default_rng(5).integers(0, 512, (64, SEQ)).astype(np.int32)
    with make_system(cfgs, params, np.array([[8, 8]]),
                     segment_size=16) as s:
        before = s.accumulator.data_messages
        Y = s.predict(X)
        assert s.accumulator.data_messages - before == 4      # 1 dev x 4 segs
        assert s.combiners and all(c.partials_posted for c in
                                   s.combiners.values())
    np.testing.assert_allclose(Y, oracle(cfgs, params, X), atol=2e-5)


def test_partial_combine_data_parallel(ens2):
    """Striping across data-parallel instances keeps per-device contribution
    counts deterministic; results still match the oracle."""
    cfgs, params = ens2
    X = np.random.default_rng(6).integers(0, 512, (100, SEQ)).astype(np.int32)
    A = np.array([[8, 8],
                  [16, 0]])
    with make_system(cfgs, params, A, segment_size=16) as s:
        before = s.accumulator.data_messages
        Y = s.predict(X)
        msgs = s.accumulator.data_messages - before
    # 7 segments: model 0 striped over 2 devices, model 1 on device 0 ->
    # device 0 posts 7 partials, device 1 posts ceil(7/2)=4 (odd segments... 3)
    assert msgs < 14                              # strictly fewer than M*segs
    np.testing.assert_allclose(Y, oracle(cfgs, params, X), atol=2e-5)


# ---- multi-request pipelining ----------------------------------------------

def test_predict_async_overlap(ens2):
    cfgs, params = ens2
    rng = np.random.default_rng(7)
    Xs = [rng.integers(0, 512, (24 + 8 * i, SEQ)).astype(np.int32)
          for i in range(5)]
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     max_in_flight=3) as s:
        handles = [s.predict_async(x) for x in Xs]
        Ys = [h.result(120.0) for h in handles]
    for x, y in zip(Xs, Ys):
        np.testing.assert_allclose(y, oracle(cfgs, params, x), atol=2e-5)


def test_inflight_window_bounded(ens2):
    cfgs, params = ens2
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     max_in_flight=2, fake=True) as s:
        # issuing many requests never exceeds the window; all complete
        handles = [s.predict_async(np.zeros((8, SEQ), np.int32))
                   for _ in range(10)]
        for h in handles:
            assert np.all(h.result(60.0) == 0)


def test_buffer_swap_race_fixed(ens2):
    """Growing a later request can't invalidate an earlier in-flight one:
    each request owns its buffer (the seed reallocated shared_x in place)."""
    cfgs, params = ens2
    rng = np.random.default_rng(8)
    small = rng.integers(0, 512, (16, SEQ)).astype(np.int32)
    big = rng.integers(0, 512, (160, SEQ)).astype(np.int32)
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     max_in_flight=4) as s:
        for _ in range(3):                 # interleave growing requests
            h_small = s.predict_async(small)
            h_big = s.predict_async(big)
            np.testing.assert_allclose(h_small.result(120.0),
                                       oracle(cfgs, params, small), atol=2e-5)
            np.testing.assert_allclose(h_big.result(120.0),
                                       oracle(cfgs, params, big), atol=2e-5)


def test_bad_members_do_not_leak_window_slots(ens2):
    """A rejected submit must release its in-flight slot, or repeated caller
    errors would wedge the window."""
    cfgs, params = ens2
    X = np.zeros((8, SEQ), np.int32)
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16,
                     fake=True, max_in_flight=2) as s:
        for _ in range(5):
            with pytest.raises(ValueError, match="out of range"):
                s.predict(X, members=[7])
        handles = [s.predict_async(X) for _ in range(4)]   # window still works
        for h in handles:
            h.result(30.0)


def test_stage_timings_populated(ens2):
    cfgs, params = ens2
    X = np.random.default_rng(9).integers(0, 512, (32, SEQ)).astype(np.int32)
    with make_system(cfgs, params, np.array([[8, 8]]), segment_size=16) as s:
        s.predict(X)
        stages = s.stage_timings()
    for key in ("inflight_wait", "linger", "batch_fill", "predict",
                "device_wait", "copy", "combine", "accumulate"):
        assert key in stages and stages[key]["count"] > 0, (key, stages)
