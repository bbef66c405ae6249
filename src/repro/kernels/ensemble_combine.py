"""Pallas TPU kernel for the paper's combination rule (§II.C.2).

The prediction accumulator's hot loop is ``Y[start(s):end(s)] += P_m / M`` for
every worker message — a weighted segment accumulation.  On TPU we fuse the
whole segment combine into one kernel: given the stacked member predictions
``P (M, seg, C)`` and combination weights ``w (M,)`` (uniform 1/M for
averaging, arbitrary for weighted averaging), produce ``Y (seg, C)``.

Two variants share the grid/tiling:
  * ``ensemble_combine(P, w)``                -> Σ_m w_m P_m  (fresh combine)
  * ``ensemble_combine(P, w, partial=Y0)``    -> Y0 + Σ_m w_m P_m
The second is the *accumulate-into-partial* form used by the device-resident
partial combine (DESIGN.md §4): workers co-located on one device fold their
weighted predictions into a running partial on-device, so only one
device->host transfer happens per device per segment instead of M.

Tiling: grid = (seg_blocks, c_blocks, M); the member dim is innermost and
sequential, accumulating into a VMEM f32 scratch tile, so each (seg, C) output
tile is written once — the memory-bound optimum (reads M·seg·C (+seg·C for the
partial), writes seg·C).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_SEG = 128
BLOCK_C = 512

# The (M,) combine weights sit whole in scalar memory and the kernel reads
# w[m] for the member on the grid: a rank-1 VMEM block of one weight breaks
# the TPU's (8, 128) tiling rule for any M > 1.
_WEIGHTS = pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(p_ref, w_ref, y_ref, acc_ref, *, members: int):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += p_ref[0].astype(jnp.float32) * w_ref[mi]

    @pl.when(mi == members - 1)
    def _finalize():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _accum_kernel(part_ref, p_ref, w_ref, y_ref, acc_ref, *, members: int):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = part_ref[...].astype(jnp.float32)

    acc_ref[...] += p_ref[0].astype(jnp.float32) * w_ref[mi]

    @pl.when(mi == members - 1)
    def _finalize():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def _quant_accum_kernel(part_ref, q_ref, s_ref, w_ref, y_ref, acc_ref, *,
                        members: int):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = part_ref[...].astype(jnp.float32)

    # Per-row dequant scale arrives replicated across the lane dim; slice
    # lane 0 and broadcast along lanes (the TPU-cheap direction).
    scale = s_ref[0][:, :1]
    deq = q_ref[0].astype(jnp.float32) * scale
    acc_ref[...] += deq * w_ref[mi]

    @pl.when(mi == members - 1)
    def _finalize():
        y_ref[...] = acc_ref[...].astype(y_ref.dtype)


def ensemble_combine_quant(partial: jax.Array, q: jax.Array,
                           scales: jax.Array, weights: jax.Array, *,
                           block_seg: int = BLOCK_SEG, block_c: int = BLOCK_C,
                           interpret: bool = False) -> jax.Array:
    """Fused dequant-weight-accumulate epilogue for quantized members.

    ``partial (seg, C) f32`` + Σ_m ``w_m · (q_m · s_m)`` where ``q (M, seg, C)``
    is int8/fp8 and ``scales (M, seg, 128) f32`` carries the per-row symmetric
    scale replicated across the lane dim (so the kernel never transposes).
    One pass: member predictions stream through VMEM once in their narrow
    storage dtype — dequantization, combine weighting, and accumulation into
    the device-resident partial all happen in-register per tile.
    """
    m, seg, c = q.shape
    block_seg = min(block_seg, seg)
    block_c = min(block_c, c)
    assert seg % block_seg == 0 and c % block_c == 0, (seg, c, block_seg, block_c)
    assert partial.shape == (seg, c), (partial.shape, seg, c)

    tile = pl.BlockSpec((block_seg, block_c), lambda s_, c_, m_: (s_, c_))
    in_specs = [
        tile,
        pl.BlockSpec((1, block_seg, block_c), lambda s_, c_, m_: (m_, s_, c_)),
        pl.BlockSpec((1, block_seg, 128), lambda s_, c_, m_: (m_, s_, 0)),
        _WEIGHTS,
    ]
    return pl.pallas_call(
        functools.partial(_quant_accum_kernel, members=m),
        grid=(seg // block_seg, c // block_c, m),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((seg, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_seg, block_c), jnp.float32)],
        interpret=interpret,
    )(partial, q, scales, weights.astype(jnp.float32))


def ensemble_combine(preds: jax.Array, weights: jax.Array,
                     partial: jax.Array = None, *,
                     block_seg: int = BLOCK_SEG, block_c: int = BLOCK_C,
                     interpret: bool = False) -> jax.Array:
    """preds: (M, seg, C); weights: (M,); optional partial: (seg, C).
    Returns (seg, C) weighted sum, plus ``partial`` when given."""
    m, seg, c = preds.shape
    block_seg = min(block_seg, seg)
    block_c = min(block_c, c)
    assert seg % block_seg == 0 and c % block_c == 0, (seg, c, block_seg, block_c)

    tile = pl.BlockSpec((block_seg, block_c), lambda s_, c_, m_: (s_, c_))
    in_specs = [
        pl.BlockSpec((1, block_seg, block_c), lambda s_, c_, m_: (m_, s_, c_)),
        _WEIGHTS,
    ]
    weights = weights.astype(jnp.float32)
    if partial is None:
        kernel = functools.partial(_kernel, members=m)
        operands = (preds, weights)
    else:
        assert partial.shape == (seg, c), (partial.shape, seg, c)
        kernel = functools.partial(_accum_kernel, members=m)
        in_specs = [tile] + in_specs
        operands = (partial, preds, weights)
    return pl.pallas_call(
        kernel,
        grid=(seg // block_seg, c // block_c, m),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((seg, c), preds.dtype),
        scratch_shapes=[pltpu.VMEM((block_seg, block_c), jnp.float32)],
        interpret=interpret,
    )(*operands)
