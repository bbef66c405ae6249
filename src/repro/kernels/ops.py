"""jit'd public wrappers around the Pallas kernels.

Responsibilities:
  * pad head_dim to a multiple of 128 (MXU lane alignment) and seq to block
    multiples, then slice results back;
  * pre-apply the softmax scale on q so zero-padding of head_dim cannot
    change results;
  * run the compiled Mosaic kernels whenever JAX's backend is the TPU, and
    the Pallas interpreter otherwise — interpret mode is the CPU-test path
    (`pallas_enabled()` reports whether the compiled TPU path is active);
    the grouped matmul alone runs ``lax.ragged_dot`` off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from repro.kernels import decode_attention as _dec
from repro.kernels import ensemble_combine as _comb
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd

_FORCE_INTERPRET = None  # test-only override, see set_interpret()


def set_interpret(value):
    """Test hook: ``False`` lowers the Mosaic kernels off-TPU (compiling for
    a described chip), ``None`` restores the default.  It cannot turn the
    interpreter on when the backend is a TPU."""
    global _FORCE_INTERPRET
    _FORCE_INTERPRET = value


def _interpret() -> bool:
    if jax.default_backend() == "tpu":
        return False
    return True if _FORCE_INTERPRET is None else _FORCE_INTERPRET


def pallas_enabled() -> bool:
    return not _interpret()


def pow2_clamp(n: int, lo: int, hi: int) -> int:
    """Next power of two >= n, clamped to [lo, hi] (block-size selection)."""
    return min(hi, max(lo, 1 << max(n - 1, 0).bit_length()))


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q: (B,S,H,hd), k/v: (B,S,KV,hd) -> (B,S,H,hd); scale 1/sqrt(hd)."""
    s, hd = q.shape[1], q.shape[3]
    q = q * (hd ** -0.5)
    bq = pow2_clamp(s, 8, _fa.BLOCK_Q)
    bkv = min(_fa.BLOCK_KV, bq)
    qp = _pad_to(_pad_to(q, 1, bq), 3, 128)
    kp = _pad_to(_pad_to(k, 1, bkv), 3, 128)
    vp = _pad_to(_pad_to(v, 1, bkv), 3, 128)
    sp = max(qp.shape[1], kp.shape[1])
    qp, kp, vp = (_pad_to(t, 1, sp) for t in (qp, kp, vp))
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              block_q=min(bq, sp), block_kv=min(bkv, sp),
                              valid_len=s, interpret=_interpret())
    return out[:, :s, :, :hd]


@jax.jit
def decode_attention(q, k, v, valid):
    """q: (B,1,H,hd), k/v: (B,L,KV,hd), valid: (L,) bool -> (B,1,H,hd)."""
    hd, L = q.shape[3], k.shape[1]
    q = q * (hd ** -0.5)
    bkv = pow2_clamp(L, 8, _dec.BLOCK_KV)
    qp = _pad_to(q, 3, 128)
    kp = _pad_to(_pad_to(k, 1, bkv), 3, 128)
    vp = _pad_to(_pad_to(v, 1, bkv), 3, 128)
    validp = _pad_to(valid.astype(jnp.int32), 0, bkv)
    out = _dec.decode_attention(qp, kp, vp, validp, block_kv=bkv,
                                interpret=_interpret())
    return out[:, :, :, :hd]


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, bmat, cmat, *, chunk: int = 64):
    """x: (B,S,H,P), dt: (B,S,H), A: (H,), bmat/cmat: (B,S,N) -> (B,S,H,P)."""
    s = x.shape[1]
    xp = _pad_to(x, 1, chunk)
    dtp = _pad_to(dt, 1, chunk)
    bp = _pad_to(bmat, 1, chunk)
    cp = _pad_to(cmat, 1, chunk)
    out = _ssd.ssd_scan(xp, dtp, A, bp, cp, chunk=chunk, interpret=_interpret())
    return out[:, :s]


def _combine_blocks(seg: int, c: int):
    """Block sizes legal for the TPU kernel at ANY (seg, C): the seg block is
    a power of two in [8, BLOCK_SEG] (sublane multiple), the class block a
    multiple of 128 in [128, BLOCK_C] (lane width).  Inputs are padded up to
    block multiples, so arbitrary segment sizes never hit the kernel's
    divisibility assert."""
    return (pow2_clamp(seg, 8, _comb.BLOCK_SEG),
            pow2_clamp(c, 128, _comb.BLOCK_C))


@jax.jit
def ensemble_combine(preds, weights):
    """preds: (M, seg, C), weights: (M,) -> (seg, C)."""
    seg, c = preds.shape[1], preds.shape[2]
    bs, bc = _combine_blocks(seg, c)
    pp = _pad_to(_pad_to(preds, 1, bs), 2, bc)
    out = _comb.ensemble_combine(pp, weights, block_seg=bs, block_c=bc,
                                 interpret=_interpret())
    return out[:seg, :c]


@jax.jit
def ensemble_accumulate(partial, preds, weights):
    """Accumulate-into-partial combine (DESIGN.md §4): ``partial (seg, C)``
    + ``preds (M, seg, C)`` weighted by ``weights (M,)`` -> (seg, C)."""
    seg, c = preds.shape[1], preds.shape[2]
    bs, bc = _combine_blocks(seg, c)
    pp = _pad_to(_pad_to(preds, 1, bs), 2, bc)
    part = _pad_to(_pad_to(partial.astype(preds.dtype), 0, bs), 1, bc)
    out = _comb.ensemble_combine(pp, weights, part, block_seg=bs, block_c=bc,
                                 interpret=_interpret())
    return out[:seg, :c]


@jax.jit
def ensemble_accumulate_quant(partial, q, scales, weights):
    """Fused dequant-weight-accumulate: ``partial (seg, C) f32`` +
    Σ_m ``w_m · (q_m · s_m)`` with ``q (M, seg, C)`` int8/fp8 and per-row
    symmetric ``scales (M, seg) f32`` -> (seg, C) f32.

    Member predictions cross VMEM in their narrow storage dtype; the seg
    block floor is 32 (int8 sublane tile) rather than 8."""
    m, seg, c = q.shape
    bs = pow2_clamp(seg, 32, _comb.BLOCK_SEG)
    bc = pow2_clamp(c, 128, _comb.BLOCK_C)
    qp = _pad_to(_pad_to(q, 1, bs), 2, bc)
    sp = _pad_to(scales.astype(jnp.float32), 1, bs)
    # replicate the per-row scale across one lane tile so the kernel reads
    # it in (sublane, lane) layout without a transpose
    sp = jnp.broadcast_to(sp[:, :, None], sp.shape + (128,))
    part = _pad_to(_pad_to(partial.astype(jnp.float32), 0, bs), 1, bc)
    out = _comb.ensemble_combine_quant(part, qp, sp, weights, block_seg=bs,
                                       block_c=bc, interpret=_interpret())
    return out[:seg, :c]


GMM_ROWS = 128              # row tile of the grouped matmul
GMM_RHS_VMEM = 8 << 20      # bytes of one double-buffered weight tile


def _gmm_tiling(k: int, n: int):
    """Row tile :data:`GMM_ROWS`; the whole contraction and as much of the
    output width as keeps the double-buffered bf16 weight tile within
    :data:`GMM_RHS_VMEM`: with one contraction tile, consecutive row tiles
    of one expert reuse the weight tile in VMEM instead of reading it
    again."""
    tn = n
    while 2 * 2 * k * tn > GMM_RHS_VMEM and tn % 256 == 0:
        tn //= 2
    return GMM_ROWS, k, tn


def _gmm(lhs, rhs, group_sizes, *, interpret: bool):
    """Megablox's grouped matmul on bf16 operands with float32 sums, rows
    padded to the row tile."""
    m = lhs.shape[0]
    lhs = _pad_to(lhs.astype(jnp.bfloat16), 0, GMM_ROWS)
    out = megablox_gmm(lhs, rhs.astype(jnp.bfloat16), group_sizes,
                       preferred_element_type=jnp.float32,
                       tiling=_gmm_tiling(lhs.shape[1], rhs.shape[2]),
                       interpret=interpret)
    return out[:m]


@jax.jit
def grouped_matmul(lhs, rhs, group_sizes):
    """lhs (m, k) rows sorted by group, rhs (G, k, n), group_sizes (G,)
    int32 -> (m, n) float32: the first ``group_sizes[0]`` rows times
    ``rhs[0]``, the next ``group_sizes[1]`` times ``rhs[1]``, and so on.
    Rows past ``sum(group_sizes)`` are unspecified.

    On the TPU, the Pallas megablox kernel fed bfloat16, which is what
    XLA's default precision feeds the MXU; elsewhere ``lax.ragged_dot`` in
    the operands' dtype."""
    if pallas_enabled():
        return _gmm(lhs, rhs, group_sizes, interpret=False)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=jnp.float32)
