"""mfu: the model FLOPs of the rows answered inside the window -- every
member, every matmul over every token, causal attention, the output head
once per row; padding and the program's logits over every position are not
counted -- over the window times the chips times the chip's peak."""
from chipbench import flops


def read(w):
    rows = sum(r.rows for r in w.completed_in_window())
    if not rows:
        return None
    work = rows * w.members * flops.model_flops_per_row(w.cfg, w.mix.seq)
    return 100.0 * work / (w.seconds * w.chips * w.peak["flops"])
