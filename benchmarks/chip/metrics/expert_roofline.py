"""expert_roofline: the grouped expert matmul's share of its roofline over
the window -- the least time its work takes on the chip, over the device
time of the configuration's ``experts`` kernel (the grouped matmul ops
inside the step) in the profiler trace, which covers the whole window.

The work comes from the program's ``expert_rows`` counter: the (token,
expert) rows the held experts computed in the window, padding rows
included, summed over layers.  Each row runs the expert's gate, up and
down matmuls, 6 x d x f FLOP; each call of the kernel reads one of a
layer's three matrices of every held expert once, E x d x f bfloat16
values; each row's input and output, d bfloat16 values each, are read and
written at least once.  The least time of the window is the larger of the
FLOPs at the chip's peak and the bytes at its HBM bandwidth, which is no
more than the sum over calls of each call's own larger term: the share
cannot pass 100 % unless the kernel's work is miscounted."""

KERNEL = "experts"


def expert_flops(cfg: dict, rows: float) -> float:
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * rows


def expert_bytes(cfg: dict, rows: float, calls: int) -> float:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2.0 * (calls * cfg["num_experts"] * d * f + rows * 2 * d)


def read(w):
    if w.trace is None:
        return None
    secs = w.trace["kernel_s"].get(KERNEL, 0.0)
    calls = w.trace["kernel_calls"].get(KERNEL, 0)
    rows = w.counter("expert_rows")
    if not secs or not calls or not rows:
        return None
    least = max(expert_flops(w.cfg, rows) / w.peak["flops"],
                expert_bytes(w.cfg, rows, calls) / w.peak["hbm_bytes_per_s"])
    return 100.0 * least / secs
