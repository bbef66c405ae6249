"""Pallas TPU flash-decoding: one query token against a long KV cache.

Tiling: grid = (batch, q_heads, num_kv_blocks); the kv-block dim is the
innermost, sequential grid dim, so the online-softmax running state lives in
VMEM scratch.  The query block (a single token per (b,h)) is tiny; the kernel
streams (BLOCK_KV, head_dim) cache tiles through VMEM — this is the
HBM-bandwidth-bound op that dominates decode_32k/long_500k rooflines.

A validity mask (int32, 1/0 per slot) handles ring-buffer SWA caches and
not-yet-filled slots.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK_KV = 512


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_ref, l_ref, acc_ref, *,
            num_kv_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # everything stays 2-D (one query row) so it maps onto (sublane, lane)
    q = q_ref[0, 0].astype(jnp.float32)            # (1, hd)
    k = k_ref[0, 0].astype(jnp.float32)            # (bkv, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    ok = valid_ref[...] > 0                        # (1, bkv)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (1, bkv)
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_ref[...]                            # (1, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + \
        jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     valid: jax.Array, *, block_kv: int = BLOCK_KV,
                     interpret: bool = False) -> jax.Array:
    """q: (B,1,H,hd); k/v: (B,L,KV,hd); valid: (L,) int32.

    L and hd must already be padded (ops.py).  Returns (B,1,H,hd).
    """
    b, _, h, hd = q.shape
    L, kv = k.shape[1], k.shape[2]
    group = h // kv
    block_kv = min(block_kv, L)
    assert L % block_kv == 0
    nk = L // block_kv

    qt = q.transpose(0, 2, 1, 3)                   # (B,H,1,hd)
    kt = k.transpose(0, 2, 1, 3)                   # (B,KV,L,hd)
    vt = v.transpose(0, 2, 1, 3)
    # (1, L) so a mask block (1, block_kv) meets the (8, 128) tiling rule:
    # its row dim equals the array's, its lane dim is a multiple of 128 or L
    valid_i = valid.astype(jnp.int32).reshape(1, L)

    kernel = functools.partial(_kernel, num_kv_blocks=nk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda b_, h_, k_: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b_, h_, k_: (b_, h_ // group, k_, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b_, h_, k_: (b_, h_ // group, k_, 0)),
            pl.BlockSpec((1, block_kv), lambda b_, h_, k_: (0, k_)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda b_, h_, k_: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt, valid_i)
    return out.transpose(0, 2, 1, 3)
