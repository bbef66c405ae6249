"""MoE layer invariants: dense vs capacity equivalence, load-balance loss,
routing properties."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import MoEConfig
from repro.models.moe import (load_balance_loss, moe_ffn, moe_ffn_dense,
                              moe_ffn_grouped)
import repro.models.transformer as T


def _setup(impl="dense", capacity_factor=1.25, experts=4, top_k=2):
    cfg = get_config("granite-moe-3b-a800m").reduced()
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, impl=impl, capacity_factor=capacity_factor,
        num_experts=experts, top_k=top_k))
    lp_shapes = T._layer_param_shapes(cfg, "attn")
    rng = jax.random.PRNGKey(0)
    lp = {}
    for i, (k, s) in enumerate(lp_shapes.items()):
        if k in ("router", "w_gate", "w_up", "w_down", "ws_gate", "ws_up",
                 "ws_down"):
            lp[k] = jax.random.normal(jax.random.fold_in(rng, i), s) * 0.05
    return cfg, lp


def test_capacity_matches_dense_when_ample():
    """With capacity >= group size no tokens drop: the GShard dispatch must
    equal the dense dropless computation exactly."""
    cfg_d, lp = _setup(impl="dense")
    cfg_c = dataclasses.replace(cfg_d, moe=dataclasses.replace(
        cfg_d.moe, impl="capacity", capacity_factor=float(cfg_d.moe.num_experts)))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg_d.d_model))
    out_d, aux_d, _ = moe_ffn(cfg_d, lp, x)
    out_c, aux_c, _ = moe_ffn(cfg_c, lp, x)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_c),
                               atol=1e-5)
    assert abs(float(aux_d) - float(aux_c)) < 1e-6


def test_capacity_drops_reduce_output_norm():
    """Tight capacity drops tokens — outputs must differ from dense."""
    cfg_d, lp = _setup(impl="dense")
    cfg_tight = dataclasses.replace(cfg_d, moe=dataclasses.replace(
        cfg_d.moe, impl="capacity", capacity_factor=0.25))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 64, cfg_d.d_model))
    out_d, _, _ = moe_ffn(cfg_d, lp, x)
    out_t, _, _ = moe_ffn(cfg_tight, lp, x)
    assert float(jnp.abs(out_d - out_t).max()) > 1e-4


def test_load_balance_loss_bounds():
    """Uniform routing -> loss ~= 1; collapsed routing -> loss ~= E."""
    E, T_, k = 8, 1024, 2
    rng = np.random.default_rng(0)
    probs_u = np.full((T_, E), 1.0 / E, np.float32)
    idx_u = np.stack([rng.permutation(E)[:k] for _ in range(T_)])
    l_u = float(load_balance_loss(jnp.asarray(probs_u), jnp.asarray(idx_u), E))
    assert abs(l_u - k) < 0.2        # f sums to k with top-k counts

    probs_c = np.zeros((T_, E), np.float32)
    probs_c[:, 0] = 1.0
    idx_c = np.zeros((T_, k), np.int64)
    l_c = float(load_balance_loss(jnp.asarray(probs_c), jnp.asarray(idx_c), E))
    assert l_c > l_u * 2             # collapse penalized


def test_topk_weights_normalized():
    from repro.models.moe import _router
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    w = jax.random.normal(jax.random.PRNGKey(2), (16, 8)) * 0.1
    weights, idx, probs = _router(x, w, 3)
    np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-5)
    assert idx.shape == (32, 3)
    assert int(idx.max()) < 8


def test_shared_expert_always_on():
    cfg = get_config("llama4-scout-17b-a16e").reduced()
    assert cfg.moe.shared_expert
    assert cfg.moe.top_k == 1


# ---- the grouped layer: one chip's share of an expert-parallel layer --------

def _grouped(cfg, held, first=0):
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, impl="grouped", num_experts=held,
        routed_experts=cfg.moe.num_experts, first_expert=first))


def _share(lp, first, held):
    """A share's weights: the whole router, its own experts."""
    return {k: v[first:first + held] if k.startswith("w_") else v
            for k, v in lp.items()}


@pytest.mark.parametrize("experts,top_k", [(4, 2), (8, 3)])
def test_grouped_with_every_expert_held_equals_dense(experts, top_k):
    """Dropless and exact: holding every expert, the grouped layer computes
    what the dense one does, and every (token, expert) assignment."""
    cfg_d, lp = _setup(impl="dense", experts=experts, top_k=top_k)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, cfg_d.d_model))
    out_d, aux_d = moe_ffn_dense(cfg_d, lp, x)
    out_g, aux_g, rows = moe_ffn_grouped(_grouped(cfg_d, experts), lp, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               atol=1e-5)
    assert abs(float(aux_g) - float(aux_d)) < 1e-6
    assert int(rows) == 2 * 24 * top_k


@pytest.mark.parametrize("unrouted", [(), (6, 7)],
                         ids=["every-share-routed", "one-share-unrouted"])
def test_expert_shares_sum_to_the_uncut_layer(unrouted):
    """Four expert-parallel shares, each holding a quarter of the experts
    behind the same router, add up to the whole layer; each assignment is
    computed by exactly one of them.  A share whose experts the router
    never picks adds exactly zero and computes no row."""
    cfg_d, lp = _setup(impl="dense", experts=8, top_k=3)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 24, cfg_d.d_model))
    if unrouted:
        # positive inputs, and a router that scores the unrouted experts
        # below zero and every other expert above it
        x = jnp.abs(x)
        sign = jnp.where(jnp.isin(jnp.arange(8), jnp.array(unrouted)), -1, 1)
        lp = dict(lp, router=jnp.abs(lp["router"]) * sign)
    whole, _ = moe_ffn_dense(cfg_d, lp, x)
    outs, rows = [], 0
    for first in range(0, 8, 2):
        out, _, r = moe_ffn_grouped(_grouped(cfg_d, 2, first),
                                    _share(lp, first, 2), x)
        out = np.asarray(out)
        if {first, first + 1} <= set(unrouted):
            assert not out.any() and int(r) == 0
        else:
            assert np.abs(out).max() > 0
        outs.append(out)
        rows += int(r)
    np.testing.assert_allclose(sum(outs), np.asarray(whole), atol=1e-5)
    assert rows == 2 * 24 * 3


def test_one_token_routed_to_every_held_expert():
    """One token whose k assignments are every expert the layer holds: every
    sorted row is held and gathered back to the same token."""
    cfg_d, lp = _setup(impl="dense", experts=4, top_k=4)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 1, cfg_d.d_model))
    out_d, _ = moe_ffn_dense(cfg_d, lp, x)
    out_g, _, rows = moe_ffn_grouped(_grouped(cfg_d, 4), lp, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               atol=1e-5)
    assert int(rows) == 4


def test_unwritten_expert_rows_never_reach_the_output(monkeypatch):
    """The TPU kernel leaves the rows past ``sum(group_sizes)`` unwritten;
    ``ragged_dot`` writes zeros there.  With NaN written there instead, a
    share's output stays finite and equals the dense layer's over the held
    experts alone."""
    from repro.kernels import ops
    real = ops.grouped_matmul

    def nan_past_groups(lhs, rhs, group_sizes):
        out = real(lhs, rhs, group_sizes)
        row = jnp.arange(out.shape[0])[:, None]
        return jnp.where(row < group_sizes.sum(), out, jnp.nan)

    monkeypatch.setattr(ops, "grouped_matmul", nan_past_groups)
    cfg_d, lp = _setup(impl="dense", experts=8, top_k=3)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 24, cfg_d.d_model))
    first, held = 2, 2
    others = jnp.ones(8).at[first:first + held].set(0)[:, None, None]
    only_held = dict(lp, w_down=lp["w_down"] * (1 - others))
    want, _ = moe_ffn_dense(cfg_d, only_held, x)
    out, _, rows = moe_ffn_grouped(_grouped(cfg_d, held, first),
                                   _share(lp, first, held), x)
    assert 0 < int(rows) < 2 * 24 * 3
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
