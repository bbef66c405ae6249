"""The serving launcher's device, chip-table and compile-cache choices, on
the CPU: nothing may quietly stand in for a TPU that is not there."""
import jax
import pytest

from repro import compile_cache
from repro.core import devices
from repro.kernels import ops
from repro.launch import serve


class _Chip:
    """A stand-in jax.Device: just what tpu_cells reads."""

    def __init__(self, kind, limit=None):
        self.device_kind = kind
        self._limit = limit

    def memory_stats(self):
        return None if self._limit is None else {"bytes_limit": self._limit}


def test_chip_table_rejects_unknown_kind():
    with pytest.raises(ValueError, match="no peak table entry"):
        devices.chip_peaks("TPU v99")
    with pytest.raises(ValueError):
        devices.tpu_cells([_Chip("TPU v99")], 1)


def test_tpu_cells_read_peaks_and_runtime_memory():
    v5e = devices.chip_peaks("TPU v5 lite")
    cells = devices.tpu_cells([_Chip("TPU v5 lite", 15 * 2 ** 30),
                               _Chip("TPU v5 lite")], 1)
    assert [c.memory_bytes for c in cells] == [15 * 2 ** 30, v5e.hbm_bytes]
    assert all(c.peak_flops == v5e.flops and c.mem_bw == v5e.hbm_bw
               for c in cells)
    pair = devices.tpu_cells([_Chip("TPU v5 lite", 10)] * 2, 2)
    assert len(pair) == 1 and pair[0].memory_bytes == 20
    assert pair[0].peak_flops == 2 * v5e.flops


def test_host_cpus_are_backed_by_the_cpu_device():
    cells = devices.host_cpus(2)
    assert all(c.jax_devices == (jax.devices("cpu")[0],) for c in cells)


def test_serve_refuses_tpu_cells_without_a_tpu(monkeypatch):
    with pytest.raises(RuntimeError, match="finds none"):
        serve.serving_devices("tpu")
    monkeypatch.setattr(serve, "wanted_platform", lambda: "tpu")
    args = serve.parse_args(["--ensemble", "ENS4", "--bench", "analytic"])
    with pytest.raises(RuntimeError, match="finds none"):
        serve.start_serving(args, alloc_cache=None)


def test_member_configs_copies_an_architecture_at_full_width():
    cfgs = serve.member_configs("qwen3-1.7b", 2)
    assert [c.d_model for c in cfgs] == [2048, 2048]
    assert len(serve.member_configs("ENS4", 3)) == 3


def test_interpreter_cannot_be_forced_on_a_tpu(monkeypatch):
    assert ops._interpret()                        # CPU default: interpret
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    ops.set_interpret(True)
    try:
        assert not ops._interpret() and ops.pallas_enabled()
    finally:
        ops.set_interpret(None)


def test_compile_cache_honours_env_else_fixed_checkout_path(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere/cache")
        assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(compile_cache.ENV)
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first.endswith("/.jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
