"""GQA self-attention (qk-norm, RoPE, sliding window), cross-attention, and
cached decode attention.

Two execution paths:
  * pure-jnp (default, shardable everywhere).  Long sequences use an online-
    softmax scan over KV chunks so the compiled memory footprint is O(S·chunk),
    never O(S^2) — the jnp analogue of the Pallas flash kernel.
  * Pallas (``repro.kernels``) when ``repro.kernels.ops.pallas_enabled()`` —
    the TPU target path, validated in interpret mode by tests.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, rms_norm

NEG_INF = -1e30
_CHUNK = 512          # KV chunk for the online-softmax scan
_DENSE_MAX = 2048     # sequences up to this use the plain masked einsum


def project_qkv(cfg: ModelConfig, p, x, kv_src=None):
    """x: (B,S,D) -> q (B,S,H,hd), k/v (B,Skv,KV,hd)."""
    kv_src = x if kv_src is None else kv_src
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _expand_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B,S,KV,hd) -> (B,S,H,hd) by repeating each kv head."""
    b, s, kv, hd = k.shape
    if kv == num_heads:
        return k
    return jnp.repeat(k, num_heads // kv, axis=2)


def _mask_bias(q_pos, k_pos, causal: bool, window: int) -> jax.Array:
    """(Sq,Sk) additive bias from position vectors."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        ok &= k_pos[None, :] > q_pos[:, None] - window
    return jnp.where(ok, 0.0, NEG_INF)


def dense_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int = 0,
                    scale: Optional[float] = None) -> jax.Array:
    """Plain masked attention.  q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd)."""
    h = q.shape[2]
    k, v = _expand_kv(k, h), _expand_kv(v, h)
    scale = scale or q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32) * scale
    logits = logits + _mask_bias(q_pos, k_pos, causal, window)[None, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqs,bshk->bqhk", probs, v)


def chunked_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int = 0,
                      chunk: int = _CHUNK) -> jax.Array:
    """Online-softmax attention scanning KV chunks; O(Sq*chunk) live memory."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    if sk % chunk:                                   # pad kv to chunk multiple
        pad = chunk - sk % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, (0, pad), constant_values=2**30)
    nk = k.shape[1] // chunk
    k = _expand_kv(k, h).reshape(b, nk, chunk, h, hd)
    v = _expand_kv(v, h).reshape(b, nk, chunk, h, hd)
    k_pos = k_pos.reshape(nk, chunk)
    scale = hd ** -0.5
    qf = q.astype(jnp.float32) * scale

    def body(carry, xs):
        m, l, acc = carry                            # (B,H,Sq), (B,H,Sq), (B,Sq,H,hd)
        kc, vc, kp = xs
        logits = jnp.einsum("bqhk,bshk->bhqs", qf, kc.astype(jnp.float32))
        logits = logits + _mask_bias(q_pos, kp, causal, window)[None, None]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + \
            jnp.einsum("bhqs,bshk->bqhk", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc), None

    init = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, sq, h, hd), jnp.float32))
    from repro import runtime_flags
    (m, l, acc), _ = jax.lax.scan(
        body, init, (k.swapaxes(0, 1), v.swapaxes(0, 1), k_pos),
        unroll=runtime_flags.scan_unroll())
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def self_attention(cfg: ModelConfig, p, x, positions, *, window: int = 0,
                   use_kernel: bool = False, yarn=None) -> jax.Array:
    """Full-sequence causal attention for train/prefill.  x: (B,S,D);
    ``yarn`` scales the layer's RoPE (full-attention layers)."""
    q, k, v = project_qkv(cfg, p, x)
    q = apply_rope(q, positions, cfg.rope_theta, yarn)
    k = apply_rope(k, positions, cfg.rope_theta, yarn)
    s = x.shape[1]
    if use_kernel:
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, window=window)
    elif s <= _DENSE_MAX:
        out = dense_attention(q, k, v, positions[0] if positions.ndim > 1 else positions,
                              positions[0] if positions.ndim > 1 else positions,
                              causal=True, window=window)
    else:
        pos1 = positions[0] if positions.ndim > 1 else positions
        out = chunked_attention(q, k, v, pos1, pos1, causal=True, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_attention(cfg: ModelConfig, p, x, frontend, *, use_kernel: bool = False):
    """x: (B,S,D) attends to frontend embeddings (B,F,fdim).  No mask, no RoPE."""
    q, k, v = project_qkv(cfg, p, x, kv_src=frontend)
    sq, sk = x.shape[1], frontend.shape[1]
    qp = jnp.arange(sq)
    kp = jnp.arange(sk)
    if max(sq, sk) <= _DENSE_MAX:
        out = dense_attention(q, k, v, qp, kp, causal=False)
    else:
        out = chunked_attention(q, k, v, qp, kp, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def decode_attention(cfg: ModelConfig, p, x, k_cache, v_cache, pos, *,
                     window: int = 0, use_kernel: bool = False,
                     k_scale=None, v_scale=None, yarn=None):
    """One-token attention against a cache.

    x: (B,1,D); k_cache/v_cache: (B,L,KV,hd) (ring buffer for SWA layers);
    pos: scalar int32 — absolute position of the new token.  With
    k_scale/v_scale ((B,L,KV,1) f32) the cache is int8 and is dequantized on
    read (beyond-paper §Perf: halves KV-streaming bytes).
    Returns (attn_out (B,1,D), new_k, new_v[, new_k_scale, new_v_scale]).
    """
    q, k_new, v_new = project_qkv(cfg, p, x)
    posv = jnp.full((x.shape[0], 1), pos)
    q = apply_rope(q, posv, cfg.rope_theta, yarn)
    k_new = apply_rope(k_new, posv, cfg.rope_theta, yarn)
    quantized = k_scale is not None
    from repro import runtime_flags
    _fd_mesh = runtime_flags.SHARDING_OPTS.get("decode_cache_seq")
    if not quantized and _fd_mesh is not None and \
            not isinstance(_fd_mesh, bool) and \
            k_cache.shape[1] % _fd_mesh.shape["model"] == 0:
        # §Perf variant "cache_seqshard": shard_map flash-decoding over a
        # sequence-sharded cache (see parallel/collectives.flash_decode).
        from repro.parallel.collectives import flash_decode
        out, k_cache, v_cache = flash_decode(
            _fd_mesh, q, k_cache, v_cache, k_new, v_new, pos, window=window)
        return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), k_cache, v_cache
    L = k_cache.shape[1]
    slot = pos % L if window > 0 else pos            # ring buffer for SWA
    if quantized:
        from repro.models.cache import dequantize_kv, quantize_kv
        kq, ks = quantize_kv(k_new[:, 0])
        vq, vs = quantize_kv(v_new[:, 0])
        k_cache = jax.lax.dynamic_update_index_in_dim(k_cache, kq, slot, 1)
        v_cache = jax.lax.dynamic_update_index_in_dim(v_cache, vq, slot, 1)
        k_scale = jax.lax.dynamic_update_index_in_dim(k_scale, ks, slot, 1)
        v_scale = jax.lax.dynamic_update_index_in_dim(v_scale, vs, slot, 1)
        k_read = dequantize_kv(k_cache, k_scale)
        v_read = dequantize_kv(v_cache, v_scale)
    else:
        k_cache = jax.lax.dynamic_update_index_in_dim(
            k_cache, k_new[:, 0].astype(k_cache.dtype), slot, 1)
        v_cache = jax.lax.dynamic_update_index_in_dim(
            v_cache, v_new[:, 0].astype(v_cache.dtype), slot, 1)
        k_read, v_read = k_cache, v_cache
    # key positions: for ring buffers reconstruct absolute positions per slot
    idx = jnp.arange(L)
    if window > 0:
        # slot i holds absolute position: the latest p <= pos with p % L == i
        k_pos = pos - ((pos - idx) % L)
    else:
        k_pos = idx
    valid = (k_pos <= pos) & (k_pos >= 0)
    if window > 0:
        valid &= k_pos > pos - window
    if use_kernel and not quantized:
        from repro.kernels import ops as kops
        out = kops.decode_attention(q, k_read, v_read, valid)
    else:
        h = q.shape[2]
        kx, vx = _expand_kv(k_read, h), _expand_kv(v_read, h)
        logits = jnp.einsum("bqhk,bshk->bhqs", q.astype(jnp.float32),
                            kx.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
        logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhqs,bshk->bqhk", probs, vx.astype(jnp.float32)).astype(q.dtype)
    attn = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if quantized:
        return attn, k_cache, v_cache, k_scale, v_scale
    return attn, k_cache, v_cache
