"""Mellum2-12B-A2.5B through the program against its plain reference
(``benchmarks/chip/configs/mellum2_ref.py``) at the reference's CPU
rehearsal size, float32 weights; YaRN against a hand computation; and what
the change to the shared paths leaves as it was for danube2: its RoPE, its
scores, its planner batch."""
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))

from chipbench import manifest  # noqa: E402

from repro.configs.base import YarnConfig  # noqa: E402
from repro.core import AllocationOptimizer, AnalyticBench  # noqa: E402
from repro.core.allocation import AllocationMatrix  # noqa: E402
from repro.core.devices import DeviceSpec, host_cpus  # noqa: E402
from repro.models import layers, transformer  # noqa: E402
from repro.serving import InferenceSystem, make_predict_fn  # noqa: E402

MAN = manifest.load_manifest()
SEQ = 16
TOL = 1e-4        # the rehearsal's limits: f32 program against f32 reference


def _config(name, rehearsal=True):
    cfg = manifest.config(MAN, name)
    ref = manifest.reference(cfg)
    cfg = ref.rehearsal(cfg) if rehearsal else cfg
    return cfg, ref, ref.program_config(cfg)


def _members(ref, cfg, n):
    return [ref.init_params(jax.random.PRNGKey(100 + m), cfg, jnp.float32)
            for m in range(n)]


def _tokens(cfg, rows, seed=0):
    return np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (rows, SEQ)).astype(np.int32)


@pytest.mark.parametrize("path", ["forward", "served"])
def test_program_matches_reference(path):
    """The program's last-position logits, through the model or through
    ``InferenceSystem`` (two members, the device combine), equal the
    reference's mean of the members' logits."""
    cfg, ref, pcfg = _config("mellum2")
    params = _members(ref, cfg, 2)
    x = _tokens(cfg, 5)
    want = np.mean([np.asarray(ref.last_logits(p, jnp.asarray(x), cfg))
                    for p in params], axis=0)
    if path == "forward":
        got = np.mean([np.asarray(transformer.last_logits(p, pcfg, x)[0])
                       for p in params], axis=0)
    else:
        alloc = AllocationMatrix(host_cpus(1, memory_bytes=1 << 30),
                                 [pcfg.name] * 2, np.array([[2, 2]]))
        with InferenceSystem([pcfg] * 2, params, alloc, segment_size=4,
                             max_seq=SEQ, combine="pallas") as system:
            got = system.predict(x)
            rows = system.serving_counters()["expert_rows"]
        # 5 rows at batch 2 run 3 chunks, 6 rows with the padding, on each
        # member; each sends between none and all of its top-k to the held
        # experts in each layer
        assert 0 < rows <= 2 * 6 * SEQ * pcfg.moe.top_k * pcfg.num_layers
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("name", ["danube2", "mellum2"])
def test_scores_are_the_last_position_of_forward(name):
    """The served scores: the final norm and the head on the last position
    alone give what the whole sequence's logits give there."""
    cfg, ref, pcfg = _config(name)
    params = _members(ref, cfg, 1)[0]
    x = _tokens(cfg, 3)
    full, _ = transformer.forward(params, pcfg, x)
    step = make_predict_fn(pcfg)
    np.testing.assert_allclose(np.asarray(step(params, x, None)),
                               np.asarray(full[:, -1, :pcfg.vocab_size]),
                               atol=1e-6, rtol=1e-6)


def test_only_a_grouped_layer_counts_expert_rows():
    """danube2's step returns its scores alone; Mellum2's returns them too
    and keeps one count per call: with every expert held, every token's
    top-k assignments in every layer."""
    cfg, ref, pcfg = _config("danube2")
    params = _members(ref, cfg, 1)[0]
    step = make_predict_fn(pcfg)
    assert not hasattr(step, "expert_rows")
    assert step(params, _tokens(cfg, 2), None).shape == (2, pcfg.vocab_size)
    cfg, ref, pcfg = _config("mellum2")
    cfg = dict(cfg, num_experts=cfg["published"]["num_experts"])
    pcfg = ref.program_config(cfg)
    params = _members(ref, cfg, 1)[0]
    step = make_predict_fn(pcfg)
    y = step(params, _tokens(cfg, 3), None)
    assert y.shape == (3, pcfg.vocab_size)
    assert [int(r) for r in step.expert_rows] == [
        3 * SEQ * pcfg.moe.top_k * pcfg.num_layers]


def test_prefill_and_decode_match_forward():
    """YaRN and the held-share expert layer reach the cached path too:
    prefill, then decoding token by token through the window's ring
    buffer, gives the whole sequence's logits at each position."""
    cfg, ref, pcfg = _config("mellum2")
    params = _members(ref, cfg, 1)[0]
    x = jnp.asarray(_tokens(cfg, 2))
    full, _ = transformer.forward(params, pcfg, x)
    lg, cache = transformer.prefill(params, pcfg, x[:, :12], 32)
    got = [lg]
    for t in range(12, SEQ):
        lg, cache = transformer.decode_step(params, pcfg, cache,
                                            x[:, t:t + 1], jnp.int32(t))
        got.append(lg)
    np.testing.assert_allclose(np.stack(got, 1), np.asarray(full[:, 11:]),
                               atol=1e-5, rtol=1e-5)


HD, THETA = 128, 500000.0
MELLUM_YARN = YarnConfig(factor=16.0, original_max_position=8192,
                         beta_fast=32.0, beta_slow=1.0,
                         attention_factor=1.2772588722239782)


def test_yarn_frequencies_and_scale_by_hand():
    """At head size 128: the dimension that turns 32 times over the 8192
    original positions is 18.08, the one that turns once 34.98, so the ramp
    runs from 18 to 35; below it YaRN keeps RoPE's frequency, above it
    divides it by 16, and in between blends the two.  cos and sin are
    scaled by the attention factor, so a rotated vector's norm is scaled
    by it too."""
    turns = [HD * math.log(8192 / (r * 2 * math.pi)) / (2 * math.log(THETA))
             for r in (32, 1)]
    assert turns == pytest.approx([18.08, 34.98], abs=0.01)
    got = np.asarray(layers.rope_freqs(HD, THETA, MELLUM_YARN), np.float64)
    for i, f in enumerate(got):
        base = THETA ** (-2 * i / HD)
        ramp = min(max((i - 18) / (35 - 18), 0.0), 1.0)
        assert f == pytest.approx(base / 16 * ramp + base * (1 - ramp),
                                  rel=1e-5)
    assert got[17] == pytest.approx(THETA ** (-34 / HD), rel=1e-6)
    assert got[36] == pytest.approx(THETA ** (-72 / HD) / 16, rel=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, HD))
    pos = jnp.arange(64)
    plain = layers.apply_rope(x, pos, THETA)
    yarn = layers.apply_rope(x, pos, THETA, MELLUM_YARN)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(yarn), axis=-1),
        1.2772588722239782 * np.linalg.norm(np.asarray(plain), axis=-1),
        rtol=1e-5)
    # the reference's own implementation agrees
    _cfg, ref, _ = _config("mellum2")
    full = manifest.config(MAN, "mellum2")["rope_parameters"]
    np.testing.assert_allclose(
        np.asarray(ref._inv_freq(HD, full["full_attention"])), got,
        rtol=1e-6)


def _parent_apply_rope(x, positions, theta):
    """``layers.apply_rope`` as it was before it took YaRN."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[..., :, None].astype(jnp.float32) * inv
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def test_danube_forward_is_bit_identical(monkeypatch):
    """danube2 gives one RoPE for every layer: its forward pass is the same,
    bit for bit, as with the RoPE it had before YaRN."""
    cfg, ref, pcfg = _config("danube2")
    assert pcfg.yarn is None
    params = _members(ref, cfg, 1)[0]
    x = _tokens(cfg, 2)
    now, _ = jax.jit(lambda p, t: transformer.forward(p, pcfg, t))(params, x)
    from repro.models import attention
    monkeypatch.setattr(
        attention, "apply_rope",
        lambda x, positions, theta, yarn: _parent_apply_rope(x, positions,
                                                             theta))
    then, _ = jax.jit(lambda p, t: transformer.forward(p, pcfg, t))(params, x)
    assert np.array_equal(np.asarray(now), np.asarray(then))


def _planner(name):
    """The benchmark's planner call on one v5e's memory (16.9e9 bytes)."""
    cfg, ref, pcfg = _config(name, rehearsal=False)
    w = next(w for w in MAN["workloads"] if w["config"] == name)
    seq = manifest.traffic_path(w["traffic"])
    from chipbench import traffic
    seq = traffic.Mix.load(seq, w["traffic"]).seq
    cfgs, dts = [pcfg] * cfg["members"], [cfg["member_dtype"]] * cfg["members"]
    chip = DeviceSpec("cell0", "TPU", int(16.9e9), 197e12, 819e9)
    return AllocationOptimizer(
        cfgs, [chip], AnalyticBench(cfgs, seq=seq, member_dtypes=dts),
        max_iter=10, max_neighs=100, seq=seq, cache_path=None,
        member_dtypes=dts).optimize().matrix, pcfg, seq


@pytest.mark.parametrize("name,batch", [("danube2", 8), ("mellum2", 2)])
def test_planner_batch(name, batch):
    """danube2 keeps both members at batch 8 on one chip; Mellum2's
    2048-token step holds its scores and expert rows, so the planner halves
    the batch until both members fit."""
    from repro.core import memory as mem
    alloc, pcfg, seq = _planner(name)
    assert alloc.A.tolist() == [[batch, batch]]
    # the dense path's float32 scores, one layer: B x heads x S x S
    assert mem._score_bytes(pcfg, 8, seq) == 8 * pcfg.num_heads * seq * seq * 4
