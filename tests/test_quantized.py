"""Quantized member execution (DESIGN.md §14): per-channel int8/fp8 params,
the fused dequant-weight-accumulate combine epilogue, precision-floor
routing, dtype-aware allocator footprints, and the live EDF dispatch queue.

Hot-path correctness contract: a quantized system's combine output tracks
the fp32 reference within quantization tolerance (per-row logit scales are
uniform across classes, so vote/argmax are unaffected), and *within* one
precision mode results stay deterministic — the chaos-band tests check
chunk replay is bit-identical and mid-flight demotion matches a direct
member subset, both under int8.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models as M
from repro.configs import ensemble
from repro.core import AllocationMatrix, host_cpus
from repro.core import memory as mem
from repro.core.worst_fit import worst_fit_decreasing
from repro.kernels import ops
from repro.kernels import quant as kq
from repro.serving.admission import DispatchQueue, EDFDispatchQueue
from repro.serving.segments import MemberUnavailable, PredictOptions
from repro.serving.system import InferenceSystem

SEQ = 16


@pytest.fixture(scope="module")
def ens2():
    cfgs = ensemble("ENS4")[:2]
    rng = jax.random.PRNGKey(0)
    params = [M.init_params(jax.random.fold_in(rng, i), c)
              for i, c in enumerate(cfgs)]
    return cfgs, params


def make_system(cfgs, params, A, **kw):
    A = np.array(A)
    devs = host_cpus(A.shape[0], memory_bytes=8 * 1024 ** 3)
    alloc = AllocationMatrix(devs, [c.name for c in cfgs], A)
    kw.setdefault("max_seq", SEQ)
    return InferenceSystem(cfgs, params, alloc, **kw)


def _X(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 512, (n, SEQ)).astype(np.int32)


# ---- shared quantization helpers ---------------------------------------------

def test_param_quantization_roundtrip():
    cfg = ensemble("ENS4")[0]
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    qp = kq.quantize_params(params, "int8")
    rp = kq.dequantize_params(qp)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(rp)):
        scale = float(jnp.abs(a).max()) or 1.0
        assert float(jnp.abs(a - b).max()) < 0.02 * scale
    # narrow storage: ~4x smaller than fp32 (scales + fp32 1-D leaves ride)
    fp32_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(params))
    assert kq.quantized_param_bytes(params, "int8") < 0.4 * fp32_bytes


def test_bf16_params_halve_bytes_and_track():
    cfg = ensemble("ENS4")[0]
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    qp = kq.quantize_params(params, "bf16")
    fp32_bytes = sum(x.size * 4 for x in jax.tree_util.tree_leaves(params))
    assert kq.quantized_param_bytes(params, "bf16") < 0.6 * fp32_bytes
    rp = kq.dequantize_params(qp)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(rp)):
        scale = float(jnp.abs(a).max()) or 1.0
        assert float(jnp.abs(a - b).max()) < 0.01 * scale


def test_meets_precision_ordering():
    assert kq.meets_precision("fp32", None)
    assert kq.meets_precision(None, "fp32")          # None member -> fp32
    assert kq.meets_precision("fp32", "int8")        # better satisfies floor
    assert kq.meets_precision("bf16", "int8")
    assert kq.meets_precision("int8", "fp8")         # int8 == fp8 rank
    assert not kq.meets_precision("int8", "bf16")
    assert not kq.meets_precision("bf16", "fp32")
    with pytest.raises(ValueError):
        kq.meets_precision("fp32", "int4")


def test_predict_options_validates_member_dtype():
    PredictOptions(member_dtype="int8")              # ok
    with pytest.raises(ValueError):
        PredictOptions(member_dtype="int4")


# ---- fused dequant-weight-accumulate epilogue --------------------------------

@pytest.mark.parametrize("m,seg,c", [(1, 8, 512), (3, 40, 512), (2, 128, 640)])
def test_fused_quant_accumulate_matches_reference(m, seg, c):
    rng = np.random.default_rng(seg)
    logits = rng.normal(size=(m, seg, c)).astype(np.float32) * 4.0
    partial = rng.normal(size=(seg, c)).astype(np.float32)
    w = rng.uniform(0.1, 1.0, m).astype(np.float32)
    qs = [kq.quantize_symmetric(jnp.asarray(x), axis=-1) for x in logits]
    q = jnp.stack([a for a, _ in qs])
    s = jnp.stack([b[:, 0] for _, b in qs])          # (m, seg)
    out = ops.ensemble_accumulate_quant(
        jnp.asarray(partial), q, s, jnp.asarray(w))
    ref = partial + sum(
        np.asarray(kq.dequantize(qs[i][0], qs[i][1])) * w[i]
        for i in range(m))
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


def test_fused_quant_fp8_matches_reference():
    rng = np.random.default_rng(7)
    logits = rng.normal(size=(2, 16, 512)).astype(np.float32)
    partial = np.zeros((16, 512), np.float32)
    qs = [kq.quantize_symmetric(jnp.asarray(x), axis=-1, dtype="fp8")
          for x in logits]
    out = ops.ensemble_accumulate_quant(
        jnp.asarray(partial), jnp.stack([a for a, _ in qs]),
        jnp.stack([b[:, 0] for _, b in qs]), jnp.full((2,), 0.5, jnp.float32))
    ref = sum(np.asarray(kq.dequantize(a, b)) * 0.5 for a, b in qs)
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-4, rtol=1e-4)


# ---- end-to-end: quantized system vs fp32 reference --------------------------

def _rel_err(y, yref):
    return float(np.abs(y - yref).max() / max(np.abs(yref).max(), 1e-6))


def test_int8_system_tracks_fp32(ens2):
    cfgs, params = ens2
    X = _X(70)
    with make_system(cfgs, params, [[8, 16]], segment_size=32) as s:
        Yref = s.predict(X)
    with make_system(cfgs, params, [[8, 16]], segment_size=32,
                     member_dtypes=["int8", "int8"]) as s:
        Y = s.predict(X)
    assert Y.shape == Yref.shape
    assert _rel_err(Y, Yref) < 0.05


@pytest.mark.parametrize("combine", ["pallas", "weighted"])
def test_int8_combine_rules_track_fp32(ens2, combine):
    cfgs, params = ens2
    X = _X(40, seed=3)
    w = np.array([0.7, 0.3], np.float32) if combine == "weighted" else None
    kw = dict(segment_size=16, combine=combine)
    if w is not None:
        kw["weights"] = w
    with make_system(cfgs, params, [[8, 8]], **kw) as s:
        Yref = s.predict(X)
    with make_system(cfgs, params, [[8, 8]],
                     member_dtypes=["int8", "int8"], **kw) as s:
        Y = s.predict(X)
    assert _rel_err(Y, Yref) < 0.05


def test_int8_vote_matches_fp32_argmax(ens2):
    """Per-row scales are positive and uniform across classes, so voting on
    the raw int8 logits preserves fp32 argmax — except where two classes sit
    within one quantization step of each other (rare near-ties may flip)."""
    cfgs, params = ens2
    X = _X(24, seed=4)
    with make_system(cfgs, params, [[8, 8]], segment_size=16,
                     combine="vote") as s:
        Yref = s.predict(X)
    with make_system(cfgs, params, [[8, 8]], segment_size=16, combine="vote",
                     member_dtypes=["int8", "int8"]) as s:
        Y = s.predict(X)
    # votes stay normalized and nearly all rows vote identically
    np.testing.assert_allclose(Y.sum(axis=1), 1.0, atol=1e-6)
    agree = (np.abs(Y - Yref).max(axis=1) < 1e-6).mean()
    assert agree >= 0.9, f"vote agreement {agree:.2f}"


def test_int8_member_subsets_track_fp32(ens2):
    cfgs, params = ens2
    X = _X(20, seed=5)
    with make_system(cfgs, params, [[8, 8]], segment_size=16) as sref, \
            make_system(cfgs, params, [[8, 8]], segment_size=16,
                        member_dtypes=["int8", "int8"]) as s:
        for members in ([0], [1], [0, 1]):
            Y = s.predict(X, members=members)
            Yref = sref.predict(X, members=members)
            assert _rel_err(Y, Yref) < 0.05, members


@pytest.mark.parametrize("combine", ["pallas", "mean"])
def test_int8_members_on_two_devices_track_fp32(ens2, combine):
    """One int8 member on each of two devices: each device's combiner folds
    its member's (q, scale) logits into its own partial, and the two
    partials add up to within int8 tolerance of the fp32 system."""
    cfgs, params = ens2
    X = _X(30, seed=6)
    kw = dict(segment_size=16, combine=combine)
    with make_system(cfgs, params, [[8, 0], [0, 8]], **kw) as s:
        Yref = s.predict(X)
    with make_system(cfgs, params, [[8, 0], [0, 8]],
                     member_dtypes=["int8", "int8"], **kw) as s:
        Y = s.predict(X)
        assert all(c.partials_posted for c in s.combiners.values())
    assert _rel_err(Y, Yref) < 0.05


def test_mixed_precision_ensemble(ens2):
    """int8 + fp32 members coexist; the combiner folds tuple and plain
    contributions into one partial."""
    cfgs, params = ens2
    X = _X(40, seed=8)
    with make_system(cfgs, params, [[8, 8]], segment_size=16) as s:
        Yref = s.predict(X)
    with make_system(cfgs, params, [[8, 8]], segment_size=16,
                     member_dtypes=["int8", "fp32"]) as s:
        Y = s.predict(X)
    assert _rel_err(Y, Yref) < 0.05


def test_h2d_staging_counter(ens2):
    """Multi-chunk segments drive the double-buffered staging path: chunk
    N+1's upload is issued while chunk N computes."""
    cfgs, params = ens2
    X = _X(128, seed=9)
    with make_system(cfgs, params, [[8, 8]], segment_size=64) as s:
        Y = s.predict(X)
        staged = sum(w.timers.counters.get("h2d_staged", 0)
                     for w in s.workers)
    assert Y.shape == (128, cfgs[0].vocab_size)
    assert staged > 0


# ---- precision-floor routing -------------------------------------------------

def test_precision_floor_filters_members(ens2):
    cfgs, params = ens2
    X = _X(20, seed=10)
    with make_system(cfgs, params, [[8, 8]], segment_size=16,
                     member_dtypes=["int8", "fp32"]) as s:
        y_fp32 = s.predict(X, options=PredictOptions(member_dtype="fp32"))
        y_m1 = s.predict(X, members=[1])
        np.testing.assert_allclose(y_fp32, y_m1, atol=1e-6)
        # floor at int8 admits everyone
        y_all = s.predict(X, options=PredictOptions(member_dtype="int8"))
        assert y_all.shape == y_fp32.shape
    with make_system(cfgs, params, [[8, 8]], segment_size=16,
                     member_dtypes=["int8", "int8"]) as s:
        with pytest.raises(MemberUnavailable):
            s.predict(X, options=PredictOptions(member_dtype="fp32"))


# ---- dtype-aware allocator ---------------------------------------------------

def test_worker_bytes_dtype_aware():
    cfg = ensemble("ENS4")[0]
    b32 = mem.worker_bytes(cfg, 8, 128)
    b8 = mem.worker_bytes(cfg, 8, 128, member_dtype="int8")
    bb = mem.worker_bytes(cfg, 8, 128, member_dtype="bf16")
    assert b8 < b32 and bb < b32
    p32 = cfg.param_count() * 4
    # params term shrinks ~4x (scale overhead <5%); activations unchanged
    assert b32 - b8 > 0.70 * p32
    assert abs((b32 - bb) - 0.5 * p32) < 0.01 * p32


def test_quantized_members_double_packing_density():
    """Worst-fit packs ~2x+ members per device once params go int8: a memory
    budget that cannot hold the ensemble at fp32 holds all of it quantized
    (at short seq the param term dominates, so int8 is ~3x denser)."""
    from repro.core.worst_fit import AllocationError
    cfgs = ensemble("ENS4")
    dts = ["int8"] * len(cfgs)
    f32 = sum(mem.worker_bytes(c, 8, SEQ) for c in cfgs)
    f8 = sum(mem.worker_bytes(c, 8, SEQ, member_dtype="int8") for c in cfgs)
    assert f8 < 0.5 * f32
    devs = host_cpus(1, memory_bytes=int(0.5 * f32))
    with pytest.raises(AllocationError):
        worst_fit_decreasing(cfgs, devs, seq=SEQ)
    a8 = worst_fit_decreasing(cfgs, devs, seq=SEQ, member_dtypes=dts)
    assert int((a8.A > 0).sum()) == len(cfgs)   # every member placed
    assert mem.fit_mem(a8, cfgs, SEQ, member_dtypes=dts)


# ---- live EDF dispatch queue -------------------------------------------------

def test_dispatch_queue_selection(ens2):
    cfgs, params = ens2
    with make_system(cfgs, params, [[8, 8]], segment_size=16) as s:
        assert all(type(w._dispatch_q) is DispatchQueue for w in s.workers)
    with make_system(cfgs, params, [[8, 8]], segment_size=16,
                     dispatch_queue="edf") as s:
        assert all(isinstance(w._dispatch_q, EDFDispatchQueue)
                   for w in s.workers)
        Y = s.predict(_X(40, seed=11))
    assert Y.shape == (40, cfgs[0].vocab_size)
    with pytest.raises(ValueError):
        make_system(cfgs, params, [[8, 8]], dispatch_queue="lifo")


def test_edf_queue_matches_fifo_results(ens2):
    """EDF only reorders dispatch; values are combine-order independent."""
    cfgs, params = ens2
    X = _X(64, seed=12)
    with make_system(cfgs, params, [[8, 8]], segment_size=16) as s:
        Yref = s.predict(X)
    with make_system(cfgs, params, [[8, 8]], segment_size=16,
                     dispatch_queue="edf") as s:
        Y = s.predict(X)
    np.testing.assert_allclose(Y, Yref, atol=1e-5)


def test_member_dtypes_validation(ens2):
    cfgs, params = ens2
    with pytest.raises(ValueError):
        make_system(cfgs, params, [[8, 8]], member_dtypes=["int8"])  # len
    with pytest.raises(ValueError):
        make_system(cfgs, params, [[8, 8]], member_dtypes=["int4", "fp32"])


# ---- chaos band: determinism within a precision mode -------------------------

@pytest.mark.chaos
def test_int8_chunk_replay_bit_identical(ens2):
    """Replay after a sibling crash re-runs the same quantized compiled fn
    at the same shape: bit-identical to a fault-free int8 run."""
    from repro.serving.faults import FaultPlan, FaultSpec
    cfgs, params = ens2
    A = [[8, 8], [8, 0]]
    Xs = [_X(8, seed=i) for i in range(8)]

    def run(fault_plan):
        s = make_system(cfgs, params, A, segment_size=8, watchdog_s=60.0,
                        supervise=True, supervise_interval_s=0.02,
                        member_dtypes=["int8", "int8"],
                        fault_plan=fault_plan)
        try:
            hs = [s.predict_async(x) for x in Xs]
            return [np.array(h.result(120.0)) for h in hs], \
                [h.quality for h in hs]
        finally:
            s.shutdown()

    base, _ = run(None)
    fp = FaultPlan(FaultSpec(stage="predictor", kind="raise", after=1,
                             worker="w1.0"))
    faulted, quals = run(fp)
    assert all(q == 1.0 for q in quals)
    for i, (yb, yf) in enumerate(zip(base, faulted)):
        np.testing.assert_array_equal(yb, yf, err_msg=f"request {i}")


@pytest.mark.chaos
def test_int8_midflight_demotion_matches_direct_subset(ens2):
    """Brownout demotion + forgiveness under quantized execution: demoting
    member 1 mid-flight equals asking for members=[0] up front, both on the
    int8 path."""
    from repro.serving.faults import FaultPlan, FaultSpec
    cfgs, params = ens2
    fp = FaultPlan(FaultSpec(stage="predictor", kind="slow", stall_s=0.05,
                             repeat=True, worker="w1"))
    s = make_system(cfgs, params, [[8, 8]], supervise=True,
                    member_dtypes=["int8", "int8"], fault_plan=fp)
    try:
        X = _X(64, seed=13)
        Yref = s.predict(X, members=[0], timeout=60.0)
        h = s.predict_async(X)
        assert s.demote_request(h.req.rid, {0})
        Y = h.result(60.0)
        assert np.allclose(Y, Yref, atol=1e-5)
        assert h.quality < 1.0
        assert s.serving_counters().get("requests_demoted") == 1
    finally:
        s.shutdown()
