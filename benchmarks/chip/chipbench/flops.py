"""Chip peaks, and the operations and bytes the work needs, from shapes.

The peak table is the benchmark's own copy, keyed by ``device_kind``; a kind
that is not in it is an error, never a default.  TPU v5e ("TPU v5 lite"):
197e12 FLOP/s in bfloat16 and 819e9 B/s of HBM bandwidth, from Google
Cloud's "TPU v5e" page.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peak table entry for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


def matmul_flops_per_token(cfg: dict) -> float:
    """2 x the parameters every token multiplies through, per layer stack:
    q, k, v, o projections and the three SwiGLU matrices."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, ff = cfg["head_dim"], cfg["intermediate_size"]
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    return 2.0 * per_layer * cfg["num_hidden_layers"]


def attention_flops_per_row(cfg: dict, seq: int) -> float:
    """Causal attention over ``seq`` tokens: each query scores the keys at
    or before it (inside the window) -- QK^T and PV, 2 FLOP per multiply-add
    each -- summed over heads and layers."""
    window = cfg.get("sliding_window") or seq
    pairs = sum(min(i + 1, window) for i in range(seq))
    return (2.0 * 2.0 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]
            * cfg["num_hidden_layers"])


def head_flops_per_row(cfg: dict) -> float:
    """The output head, once per row: only the last position is scored."""
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def model_flops_per_row(cfg: dict, seq: int) -> float:
    """What one member needs to score one row of ``seq`` tokens.  The
    program's logits over every position, and padding rows, are waste and
    are not counted."""
    return (seq * matmul_flops_per_token(cfg)
            + attention_flops_per_row(cfg, seq) + head_flops_per_row(cfg))


def combine_bytes(rows: int, classes: int, members: int = 1) -> int:
    """Bytes the accumulate-into-partial combine must move for one call:
    read the float32 partial and ``members`` float32 predictions, write the
    partial."""
    return 4 * rows * classes * (members + 2)
