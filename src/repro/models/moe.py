"""Mixture-of-Experts layer: top-k router + GShard-style capacity dispatch,
or a dropless grouped layer over the experts one instance holds.

The dispatch/combine formulation uses one-hot einsums over (group, capacity)
so that sharding experts over the "model" mesh axis induces all-to-all — the
TPU-native expert-parallel pattern — instead of gathers XLA cannot shard.
Tokens are processed in groups of ``GROUP`` so dispatch cost stays linear in
sequence length.

The grouped layer (``impl="grouped"``, :func:`moe_ffn_grouped`) is one
chip's share of an expert-parallel deployment: the router scores every
expert, the (token, expert) assignments to held experts are sorted by expert
and run through a grouped matmul, and assignments to absent experts add
nothing (their chips would add them).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig

GROUP = 512


def _router(x, w_router, top_k: int):
    """x: (T,D) -> (weights (T,k), idx (T,k), probs (T,E))."""
    logits = jnp.einsum("td,de->te", x, w_router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, idx = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights.astype(x.dtype), idx, probs


def load_balance_loss(probs: jax.Array, idx: jax.Array, num_experts: int) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * p_e."""
    f = jnp.mean(jax.nn.one_hot(idx, num_experts, dtype=jnp.float32).sum(-2), axis=0)
    p = jnp.mean(probs, axis=0)
    return num_experts * jnp.sum(f * p)


def moe_ffn_dense(cfg: ModelConfig, p, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Dropless exact MoE: every expert computed for every token, combined by
    the top-k router weights.  O(E) compute — the correctness/CPU path."""
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    weights, idx, probs = _router(xt, p["router"], m.top_k)
    aux = load_balance_loss(probs, idx, m.num_experts) * m.router_aux_coef
    # (T,E) combine weights from scattered top-k
    wfull = jnp.zeros((xt.shape[0], m.num_experts), x.dtype).at[
        jnp.arange(xt.shape[0])[:, None], idx].add(weights)
    h = jnp.einsum("td,edf->tef", xt, p["w_gate"])
    u = jnp.einsum("td,edf->tef", xt, p["w_up"])
    eo = jnp.einsum("tef,efd->ted", jax.nn.silu(h) * u, p["w_down"])
    out = jnp.einsum("te,ted->td", wfull, eo).reshape(b, s, d)
    if m.shared_expert:
        sh = jnp.einsum("bsd,df->bsf", x, p["ws_gate"])
        su = jnp.einsum("bsd,df->bsf", x, p["ws_up"])
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(sh) * su, p["ws_down"])
    return out, aux


def moe_ffn_grouped(cfg: ModelConfig, p, x: jax.Array
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless layer over the held experts: x (B,S,D) -> (out (B,S,D),
    aux_loss scalar, rows int32), ``rows`` being the (token, expert)
    assignments the held experts computed.

    The router's softmax runs over all ``router_width`` experts in float32,
    then top-k and renormalise; every assignment to a held expert is
    computed, with no capacity.  The assignments are sorted by expert (the
    absent ones last), each held expert's SwiGLU runs on its own rows
    through :func:`repro.kernels.ops.grouped_matmul`, and each token gathers
    its held assignments' output rows back from the sorted order and sums
    them times their router weights."""
    from repro.kernels import ops as kops
    m = cfg.moe
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    weights, idx, probs = _router(xt, p["router"], m.top_k)
    aux = load_balance_loss(probs, idx, m.router_width) * m.router_aux_coef
    local = idx.reshape(-1) - m.first_expert             # (T*k,)
    held = (local >= 0) & (local < m.num_experts)
    key = jnp.where(held, local, m.num_experts)          # absent: one past
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=m.num_experts + 1)[:-1].astype(jnp.int32)
    tok = order // m.top_k                              # token of each row
    xs = xt[tok]
    h = kops.grouped_matmul(xs, p["w_gate"], sizes)
    u = kops.grouped_matmul(xs, p["w_up"], sizes)
    y = kops.grouped_matmul(jax.nn.silu(h) * u, p["w_down"], sizes)
    # un-sort by a gather, not a scatter-add of d-wide rows.  A held
    # assignment's sorted row is its expert's offset plus its stable rank
    # among that expert's rows; absent ones point past the end and take
    # the fill, so rows the kernel left unwritten never reach the output
    onehot = jax.nn.one_hot(key, m.num_experts, dtype=jnp.int32)
    rank = ((jnp.cumsum(onehot, axis=0) - onehot) * onehot).sum(1)
    start = jnp.cumsum(sizes) - sizes
    pos = jnp.where(held, start[key] + rank, key.shape[0]).reshape(-1, m.top_k)
    yk = jnp.take(y, pos, axis=0, mode="fill", fill_value=0.0)    # (T,k,D)
    out = (yk * weights.astype(jnp.float32)[..., None]).sum(1)
    return out.astype(x.dtype).reshape(b, s, d), aux, sizes.sum()


def moe_ffn(cfg: ModelConfig, p, x: jax.Array
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,D) -> (out (B,S,D), aux_loss scalar, expert rows the grouped
    layer computed; 0 for the dense and capacity paths)."""
    m = cfg.moe
    if m.impl == "dense":
        return (*moe_ffn_dense(cfg, p, x), 0)
    if m.impl == "grouped":
        return moe_ffn_grouped(cfg, p, x)
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    weights, idx, probs = _router(xt, p["router"], m.top_k)
    aux = load_balance_loss(probs, idx, m.num_experts) * m.router_aux_coef

    g = min(GROUP, t)
    ng = t // g
    rem = t - ng * g
    if rem:                                    # pad to a whole number of groups
        pad = g - rem
        xt = jnp.pad(xt, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
        idx = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=0)
        # padded tokens get zero combine weight
        weights = weights * (jnp.arange(xt.shape[0]) < t)[:, None].astype(weights.dtype)
        ng += 1
    cap = max(1, int(m.capacity_factor * m.top_k * g / m.num_experts))
    cap = min(cap, g)

    xg = xt.reshape(ng, g, d)
    wg = weights.reshape(ng, g, m.top_k)
    ig = idx.reshape(ng, g, m.top_k)

    # §Perf variant "moe_ep": explicit GShard expert-parallel constraints.
    # Without them GSPMD falls back to involuntary full rematerialization of
    # the dispatch tensors (see EXPERIMENTS.md §Perf / llama4-scout).
    from repro import runtime_flags
    _mesh = runtime_flags.SHARDING_OPTS.get("moe_constraints")

    def _c(t, *spec):
        if _mesh is None:
            return t
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.mesh import batch_axes
        bax = batch_axes(_mesh)
        bax = bax if len(bax) > 1 else (bax[0] if bax else None)
        full = []
        for dim, s in enumerate(spec):
            s = bax if s == "B" else s
            size = 1
            for a in ((s,) if isinstance(s, str) else (s or ())):
                size *= _mesh.shape[a]
            full.append(s if s and t.shape[dim] % size == 0 else None)
        return jax.lax.with_sharding_constraint(
            t, NamedSharding(_mesh, PartitionSpec(*full)))

    xg = _c(xg, "B", None, None)

    # position of each (token, k) inside its expert's capacity buffer
    onehot = jax.nn.one_hot(ig, m.num_experts, dtype=jnp.int32)      # (ng,g,k,E)
    # rank among same-expert assignments, k-major so higher-priority k wins slots
    flat = onehot.transpose(0, 2, 1, 3).reshape(ng, g * m.top_k, m.num_experts)
    ranks = jnp.cumsum(flat, axis=1) - flat                          # (ng,g*k,E)
    pos = (ranks * flat).sum(-1).reshape(ng, m.top_k, g).transpose(0, 2, 1)
    keep = pos < cap
    expert_of = ig
    # dispatch tensor (ng, g, E, C)
    disp = (jax.nn.one_hot(expert_of, m.num_experts, dtype=xt.dtype)[..., None] *
            jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                           dtype=xt.dtype)[..., None, :-1]).sum(2)   # sum over k
    comb = (wg[..., None, None] *
            jax.nn.one_hot(expert_of, m.num_experts, dtype=xt.dtype)[..., None] *
            jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                           dtype=xt.dtype)[..., None, :-1]).sum(2)

    disp = _c(disp, "B", None, "model", None)
    comb = _c(comb, "B", None, "model", None)
    ex = jnp.einsum("tgec,tgd->tecd", disp, xg)                      # (ng,E,C,D)
    ex = _c(ex, "B", "model", None, None)         # all-to-all: tokens -> experts
    h = jnp.einsum("tecd,edf->tecf", ex, p["w_gate"])
    u = jnp.einsum("tecd,edf->tecf", ex, p["w_up"])
    eo = jnp.einsum("tecf,efd->tecd", jax.nn.silu(h) * u, p["w_down"])
    eo = _c(eo, "B", "model", None, None)
    out = jnp.einsum("tgec,tecd->tgd", comb, eo)                     # (ng,g,D)
    out = _c(out, "B", None, None)
    out = out.reshape(-1, d)[:t].reshape(b, s, d)

    if m.shared_expert:
        sh = jnp.einsum("bsd,df->bsf", x, p["ws_gate"])
        su = jnp.einsum("bsd,df->bsf", x, p["ws_up"])
        out = out + jnp.einsum("bsf,fd->bsd", jax.nn.silu(sh) * su, p["ws_down"])
    return out, aux, 0
