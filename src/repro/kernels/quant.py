"""Shared quantization helpers (per-channel symmetric int8 / fp8).

Single home for the reduced-precision math used across the stack:

  * the int8 KV decode cache (``models/cache.py`` re-exports
    :func:`quantize_kv` / :func:`dequantize_kv` from here),
  * quantized member execution in the serving worker (weight-only
    per-output-channel param quantization + per-row logit quantization
    feeding the fused dequant-weight-accumulate combine epilogue in
    ``kernels/ensemble_combine.py``),
  * the allocator's dtype-size-aware memory footprints
    (:func:`dtype_bytes`).

Symmetric scheme throughout: ``scale = max(|x|, axis) / qmax`` (clamped to
1e-8 so all-zero channels stay finite), ``q = clip(round(x / scale))``.
int8 uses qmax=127; fp8 (e4m3) uses qmax=448 and stores the scaled value
directly in the narrow float format (no rounding step needed — the cast
rounds).
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

# Bytes per parameter for each supported member execution dtype.  Serving
# activations stay fp32 regardless; this table governs param storage (and
# therefore H2D traffic and packing density in the allocator).
MEMBER_DTYPES = {"fp32": 4, "bf16": 2, "int8": 1, "fp8": 1}

_FP8_DTYPE = jnp.float8_e4m3fn
_FP8_MAX = 448.0  # largest finite e4m3 value


def validate_member_dtype(name: str) -> str:
    """Check ``name`` is a supported member dtype; returns it unchanged."""
    if name not in MEMBER_DTYPES:
        raise ValueError(
            f"unknown member dtype {name!r}; expected one of "
            f"{sorted(MEMBER_DTYPES)}")
    return name


def dtype_bytes(name: Optional[str]) -> int:
    """Param bytes-per-element for a member dtype (None -> fp32)."""
    if name is None:
        return MEMBER_DTYPES["fp32"]
    return MEMBER_DTYPES[validate_member_dtype(name)]


def is_quantized_dtype(name: Optional[str]) -> bool:
    return name in ("int8", "fp8")


# precision ordering for PredictOptions.member_dtype ("at this precision or
# better"): fp32 > bf16 > int8 == fp8
_PRECISION_RANK = {"fp32": 3, "bf16": 2, "int8": 1, "fp8": 1}


def meets_precision(member_dtype: Optional[str],
                    floor: Optional[str]) -> bool:
    """True when a member executing at ``member_dtype`` (None -> fp32)
    satisfies a request's minimum-precision ``floor`` (None -> any)."""
    if floor is None:
        return True
    have = _PRECISION_RANK[member_dtype or "fp32"]
    return have >= _PRECISION_RANK[validate_member_dtype(floor)]


# --------------------------------------------------------------------------
# Core per-channel symmetric quantization
# --------------------------------------------------------------------------
def quantize_symmetric(x: jax.Array, axis: int = -1,
                       dtype: str = "int8") -> Tuple[jax.Array, jax.Array]:
    """Per-channel symmetric quantization along ``axis``.

    Returns ``(q, scale)`` with ``scale`` keeping a size-1 dim on ``axis``
    so ``q * scale`` broadcasts back to ``x``'s shape.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    if dtype == "int8":
        scale = jnp.maximum(amax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    elif dtype == "fp8":
        scale = jnp.maximum(amax / _FP8_MAX, 1e-8)
        q = (xf / scale).astype(_FP8_DTYPE)
    else:
        raise ValueError(f"quantize_symmetric: unsupported dtype {dtype!r}")
    return q, scale


def dequantize(q: jax.Array, scale: jax.Array,
               dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`quantize_symmetric` (lossy)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# KV-cache aliases (historical home: models/cache.py)
# --------------------------------------------------------------------------
def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-(head-)channel int8 over the trailing dim."""
    return quantize_symmetric(x, axis=-1, dtype="int8")


def dequantize_kv(q: jax.Array, scale: jax.Array,
                  dtype=jnp.float32) -> jax.Array:
    return dequantize(q, scale, dtype)


# --------------------------------------------------------------------------
# Weight-only param quantization (serving worker)
# --------------------------------------------------------------------------
# Quantized param trees wrap every leaf in a small dict so the original
# pytree structure is recoverable and the whole thing moves over H2D as one
# device_put: ``{"q": int8/fp8, "s": f32 scales}`` for quantized leaves,
# ``{"w": array}`` for passthrough.  Matrix-shaped leaves (ndim >= 2) are
# quantized per output channel (last axis); 1-D leaves (norm gains, biases,
# dt/A/D vectors) are precision-sensitive and tiny, so they ride along in
# fp32.  All wrapped-dict values are arrays, so device_put works unchanged.
def _is_wrapped(node: Any) -> bool:
    if not isinstance(node, dict):
        return False
    keys = set(node)
    return keys == {"q", "s"} or keys == {"w"}


def quantize_params(params: Any, dtype: str = "int8") -> Any:
    """Wrap a param pytree for reduced-precision storage.

    ``dtype`` in {"int8", "fp8"} quantizes matrix leaves per output channel;
    "bf16" casts matrix leaves; "fp32" wraps without conversion (useful for
    uniform handling).  Undo with :func:`dequantize_params`.
    """
    validate_member_dtype(dtype)

    def wrap(x):
        x = jnp.asarray(x)
        if x.ndim < 2 or dtype == "fp32":
            return {"w": x}
        if dtype == "bf16":
            return {"w": x.astype(jnp.bfloat16)}
        q, s = quantize_symmetric(x, axis=-1, dtype=dtype)
        return {"q": q, "s": s}

    return jax.tree_util.tree_map(wrap, params)


def dequantize_params(qparams: Any, dtype=jnp.float32) -> Any:
    """Recover a compute-dtype param pytree from :func:`quantize_params`.

    Traceable — call inside jit so dequantization fuses into the forward
    pass (weight-only quantization: storage and transfer are narrow, math
    is fp32).
    """
    def unwrap(node):
        if "w" in node:
            return node["w"].astype(dtype) if node["w"].dtype != dtype \
                else node["w"]
        return dequantize(node["q"], node["s"], dtype)

    return jax.tree_util.tree_map(unwrap, qparams, is_leaf=_is_wrapped)


def quantized_param_bytes(params: Any, dtype: str = "int8") -> int:
    """Bytes the wrapped tree occupies on device (q + scales + fp32 rest)."""
    wrapped = quantize_params(params, dtype)
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(wrapped)
               if hasattr(x, "dtype"))
