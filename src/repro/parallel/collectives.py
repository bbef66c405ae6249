"""Explicit collective patterns (shard_map) that GSPMD cannot discover.

``flash_decode``: one-token attention against a sequence-sharded KV cache.
Each chip owns an L/n slice of the cache (n = "model" axis): the cache update
touches only the owning chip, attention reads are chip-local, and the online
softmax combines with tiny (B,H)-sized pmax/psum — replacing the involuntary
cache all-gather GSPMD emits for a dynamically-indexed sharded ring buffer
(measured: 2.1 GiB -> ~100 KiB per layer per step on qwen3 decode_32k,
EXPERIMENTS.md §Perf)."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import batch_axes

NEG_INF = -1e30


def flash_decode(mesh, q, k_cache, v_cache, k_new, v_new, pos, *,
                 window: int = 0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """q: (B,1,H,hd); k_cache/v_cache: (B,L,KV,hd) seq-sharded over "model";
    k_new/v_new: (B,1,KV,hd); pos: scalar int32.

    Returns (out (B,1,H,hd), new_k_cache, new_v_cache).  RoPE/qk-norm must
    already be applied.  Handles full caches (window=0, slot=pos) and SWA
    ring buffers (slot=pos%L) with the same absolute-position masking as the
    single-device path.
    """
    L = k_cache.shape[1]
    n = mesh.shape["model"]
    l_local = L // n
    bax = batch_axes(mesh)
    bspec = bax if len(bax) > 1 else (bax[0] if bax else None)
    cache_spec = P(bspec, "model", None, None)
    rep_spec = P(bspec, None, None, None)

    def local(q, kc, vc, kn, vn, pos):
        idx = jax.lax.axis_index("model")
        lo = idx * l_local
        slot_global = pos % L if window > 0 else pos
        slot = slot_global - lo
        in_range = (slot >= 0) & (slot < l_local)
        slot_c = jnp.clip(slot, 0, l_local - 1)
        kc_up = jax.lax.dynamic_update_index_in_dim(
            kc, kn[:, 0].astype(kc.dtype), slot_c, 1)
        vc_up = jax.lax.dynamic_update_index_in_dim(
            vc, vn[:, 0].astype(vc.dtype), slot_c, 1)
        kc = jnp.where(in_range, kc_up, kc)
        vc = jnp.where(in_range, vc_up, vc)
        # absolute positions of local slots
        gidx = lo + jnp.arange(l_local)
        if window > 0:
            k_pos = pos - ((pos - gidx) % L)
        else:
            k_pos = gidx
        valid = (k_pos <= pos) & (k_pos >= 0)
        if window > 0:
            valid &= k_pos > pos - window
        h = q.shape[2]
        kv = kc.shape[2]
        kx = kc if kv == h else jnp.repeat(kc, h // kv, axis=2)
        vx = vc if kv == h else jnp.repeat(vc, h // kv, axis=2)
        logits = jnp.einsum("bqhk,bshk->bhqs", q.astype(jnp.float32),
                            kx.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        m_loc = logits.max(axis=-1)                      # (B,H,1)
        m = jax.lax.pmax(m_loc, "model")
        p = jnp.exp(logits - m[..., None])
        p = jnp.where(valid[None, None, None, :], p, 0.0)
        l_loc = p.sum(axis=-1)                           # (B,H,1)
        acc = jnp.einsum("bhqs,bshk->bqhk", p, vx.astype(jnp.float32))
        l_tot = jax.lax.psum(l_loc, "model")
        acc = jax.lax.psum(acc, "model")
        out = acc / jnp.maximum(l_tot, 1e-30).transpose(0, 2, 1)[..., None]
        return out.astype(q.dtype), kc, vc

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rep_spec, cache_spec, cache_spec, rep_spec, rep_spec, P()),
        out_specs=(rep_spec, cache_spec, cache_spec),
        check_vma=False)
    return fn(q, k_cache, v_cache, k_new, v_new, pos)
