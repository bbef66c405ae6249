"""Memory model: does an allocation matrix fit? (paper's ``fit_mem``).

Per-worker footprint = params + activation workspace (batch-dependent) +
decode KV/SSM cache (batch- and seq-dependent — our beyond-paper extension
for stateful LLM serving, DESIGN.md §9.3).

Param storage is dtype-size-aware (DESIGN.md §14): a member executing at
int8/fp8 holds its weights at 1 byte/param (+~3% for the per-channel scales)
while activations stay at the compute dtype, so quantized members roughly
double worst-fit packing density.  Pass ``member_dtypes`` (one dtype name
per model, None entries meaning fp32) to the allocation-level predicates.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

from repro.configs.base import ModelConfig
from repro.core.allocation import AllocationMatrix
from repro.core.devices import DeviceSpec
from repro.kernels.quant import dtype_bytes as _param_dtype_bytes

# per-channel scale overhead of the quantized param layout (one f32 per
# output channel; ~1/32 of the int8 payload at typical channel widths)
_SCALE_OVERHEAD = 1.03


def _param_bytes_per_elem(member_dtype: Optional[str],
                          dtype_bytes: int) -> float:
    """Bytes per param element for a member dtype (None -> the activation
    dtype, preserving the historical fp32-params assumption)."""
    if member_dtype is None:
        return dtype_bytes
    b = _param_dtype_bytes(member_dtype)
    return b * _SCALE_OVERHEAD if b == 1 else b


def worker_bytes(cfg: ModelConfig, batch: int, seq: int,
                 dtype_bytes: int = 4, *, serving_cache_len: int = 0,
                 member_dtype: Optional[str] = None) -> int:
    """Footprint of one worker (one model instance at one batch size)."""
    params = int(cfg.param_count()
                 * _param_bytes_per_elem(member_dtype, dtype_bytes))
    # activation workspace: residual + mixer + mlp peaks per layer (x2 for
    # double-buffering); heads term covers attention q/k/v blocks
    per_tok = (4 * cfg.d_model
               + (cfg.d_ff if cfg.moe is None else _expert_per_tok(cfg))
               + 2 * cfg.num_heads * cfg.hd
               + (2 * cfg.d_inner if cfg.ssm else 0))
    acts = 2 * batch * seq * per_tok * dtype_bytes
    logits = batch * cfg.padded_vocab * dtype_bytes
    cache = cfg.kv_cache_bytes(batch, serving_cache_len or seq, 2) \
        if serving_cache_len else 0
    return params + acts + _score_bytes(cfg, batch, seq) + logits + cache


def _expert_per_tok(cfg: ModelConfig) -> int:
    """Expert-layer workspace per token, in elements.  The grouped layer
    holds every one of a token's top-k assignments as a row, whichever
    expert it goes to: its input and output (d_model each) and the
    SwiGLU's gate, up and product (d_ff_expert each)."""
    m = cfg.moe
    shared = m.d_ff_shared if m.shared_expert else 0
    if m.impl == "grouped":
        return m.top_k * (2 * cfg.d_model + 3 * m.d_ff_expert) + shared
    return m.top_k * m.d_ff_expert + shared


def _score_bytes(cfg: ModelConfig, batch: int, seq: int) -> int:
    """The attention scores of one layer, float32: the whole (seq, seq)
    square per head up to ``attention._DENSE_MAX`` positions, where the
    step takes the plain masked path, and one KV chunk's columns above."""
    from repro.models import attention
    if not cfg.has_attention:
        return 0
    cols = seq if seq <= attention._DENSE_MAX else attention._CHUNK
    return batch * cfg.num_heads * seq * cols * 4


def device_usage(alloc: AllocationMatrix, cfgs: Sequence[ModelConfig],
                 seq: int, dtype_bytes: int = 4,
                 member_dtypes: Optional[Sequence[Optional[str]]] = None
                 ) -> List[int]:
    """Bytes used per device under matrix ``alloc``."""
    usage = [0] * len(alloc.devices)
    for d, m, batch in alloc.workers():
        usage[d] += worker_bytes(
            cfgs[m], batch, seq, dtype_bytes,
            member_dtype=member_dtypes[m] if member_dtypes else None)
    return usage


def fit_mem(alloc: AllocationMatrix, cfgs: Sequence[ModelConfig], seq: int,
            dtype_bytes: int = 4,
            member_dtypes: Optional[Sequence[Optional[str]]] = None) -> bool:
    """The paper's feasibility predicate."""
    usage = device_usage(alloc, cfgs, seq, dtype_bytes, member_dtypes)
    return all(u <= dev.memory_bytes
               for u, dev in zip(usage, alloc.devices))


def remaining_memory(alloc: AllocationMatrix, cfgs: Sequence[ModelConfig],
                     seq: int, dtype_bytes: int = 4,
                     member_dtypes: Optional[Sequence[Optional[str]]] = None
                     ) -> List[int]:
    usage = device_usage(alloc, cfgs, seq, dtype_bytes, member_dtypes)
    return [dev.memory_bytes - u for u, dev in zip(usage, alloc.devices)]
