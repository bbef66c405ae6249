"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Tiling: grid = (batch, heads, num_chunks); the chunk dim is the innermost
sequential grid dim, so each head's inter-chunk SSM state (P, N) is carried
in VMEM scratch (f32).  Each kernel invocation computes one head's dual form
over one chunk:

    y_intra = (C B^T ∘ L) (dt x)        — attention-like, MXU matmuls
    y_inter = C h_in * exp(cumsum dA)   — contribution of the carried state
    h_out   = h_in * exp(sum dA) + (dt decay x)^T B

Heads lead the layout (the wrapper transposes x to (B, H, S, P)), so every
block is a 2-D (time, feature) tile.  For mamba2-1.3b a state tile is
64*128*4B = 32 KiB and a chunk tile 16 KiB, far inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
            chunk: int):
    """One (batch, head, chunk) step.  Every value is 2-D: time runs down
    the sublanes of the column operands and along the lanes of the row
    operands, so no op needs a transpose or a 3-D layout."""
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[hi]                                   # this head's A (SMEM)
    x = x_ref[0, 0].astype(jnp.float32)             # (cl, P)
    dt_col = dtc_ref[0, 0].astype(jnp.float32)      # (cl, 1)
    dt_row = dtr_ref[0, 0, pl.ds(ci, 1), :].astype(jnp.float32)   # (1, cl)
    bm = b_ref[0].astype(jnp.float32)               # (cl, N)
    cm = c_ref[0].astype(jnp.float32)               # (cl, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row >= col                                # (i, j): j <= i
    # inclusive prefix sums of dA, as a column and as a row (Mosaic has no
    # cumsum lowering; a masked reduction over the (cl, cl) tile is exact)
    cs_col = jnp.where(tri, dt_row * a, 0.0).sum(axis=1, keepdims=True)
    cs_row = jnp.where(row <= col, dt_col * a, 0.0).sum(axis=0, keepdims=True)
    total = (dt_row * a).sum(axis=1, keepdims=True)                 # (1, 1)

    # intra-chunk: (C B^T ∘ L) (dt x)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (cl, cl)
    decay = jnp.where(tri, jnp.exp(cs_col - cs_row), 0.0)
    xdt = x * dt_col                                                  # (cl, P)
    y = jnp.dot(scores * decay, xdt, preferred_element_type=jnp.float32)
    # inter-chunk: C h_in^T, decayed from the chunk start
    h_in = h_ref[...]                                                 # (P, N)
    y = y + jax.lax.dot_general(cm, h_in, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * \
        jnp.exp(cs_col)
    y_ref[0, 0] = y.astype(y_ref.dtype)
    # state update: h_out = h_in exp(sum dA) + (dt x decay-to-end)^T B
    tail = xdt * jnp.exp(total - cs_col)                              # (cl, P)
    h_ref[...] = h_in * jnp.exp(total) + jax.lax.dot_general(
        tail, bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, bmat: jax.Array,
             cmat: jax.Array, *, chunk: int = 64,
             interpret: bool = False) -> jax.Array:
    """x: (B,S,H,P) f32, dt: (B,S,H) post-softplus, A: (H,) negative,
    bmat/cmat: (B,S,N).  S must be a multiple of ``chunk`` (ops.py pads).
    Returns y: (B,S,H,P) f32."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    assert s % chunk == 0
    nc = s // chunk

    xt = x.transpose(0, 2, 1, 3)                    # (B,H,S,P)
    dtt = dt.transpose(0, 2, 1)                     # (B,H,S)
    dt_col = dtt[..., None]                         # (B,H,S,1)
    dt_rows = dtt.reshape(b, h, nc, chunk)          # (B,H,nc,cl)
    kernel = functools.partial(_kernel, chunk=chunk)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, nc, chunk), lambda b_, h_, c_: (b_, h_, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p),
                               lambda b_, h_, c_: (b_, h_, c_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dt_col, dt_rows, A.astype(jnp.float32), bmat, cmat)
    return out.transpose(0, 2, 1, 3)
