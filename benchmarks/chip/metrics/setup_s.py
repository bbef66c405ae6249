"""setup_s: seconds from process start to the window's opening -- TPU
start-up, planning, weights, InferenceSystem and every warm-up."""


def read(w):
    return w.setup_s
