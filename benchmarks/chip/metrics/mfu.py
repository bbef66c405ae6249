"""mfu: the model FLOPs of the rows answered inside the window -- every
member, each row counted by the configuration's reference module
(``model_flops_per_row``; for a dense decoder every matmul over every
token, causal attention, the output head once per row); padding and the
program's logits over every position are not counted -- over the window
times the chips times the chip's peak."""
from chipbench import manifest


def read(w):
    rows = sum(r.rows for r in w.completed_in_window())
    if not rows:
        return None
    per_row = manifest.reference(w.cfg).model_flops_per_row(w.cfg, w.mix.seq)
    work = rows * w.members * per_row
    return 100.0 * work / (w.seconds * w.chips * w.peak["flops"])
