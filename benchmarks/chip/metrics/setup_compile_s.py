"""setup_compile_s: seconds set-up spent compiling programs or loading them
from JAX's persistent cache, from JAX's monitoring events before the
window (a program loaded from the cache counts its load time)."""


def read(w):
    c = w.setup_compiles
    return c["compile_s"]
