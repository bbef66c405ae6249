"""The TPU compiler accepts every Pallas kernel of ``kernels/ops.py`` at the
widths the serving path runs them: compiled for a described v5e chip, no chip
attached.  Interpret-mode tests (test_kernels.py) cannot see the tiling and
lowering rules these compiles enforce.

All such compiles live in this one file: only one process at a time may load
the TPU compiler's library, so the topology is described inside a fixture of
this module (never at import), and the module skips where it cannot be."""
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import MoEConfig
from repro.kernels import ops
from repro.models.moe import moe_ffn_grouped

VOCAB = 151936            # qwen3-1.7b's class count: the combine's C
SEG = 32                  # the serving segment size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Lower the Mosaic kernels (not the interpreter) for one chip, with the
    persistent compilation cache off: entries written for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    ops.set_interpret(False)
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        ops.set_interpret(None)
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *args, **static):
    hlo = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("members", [2, 4])
def test_ensemble_combine_compiles(one_chip, members):
    _compile(ops.ensemble_combine, _s(one_chip, (members, SEG, VOCAB)),
             _s(one_chip, (members,)))


def test_ensemble_accumulate_compiles(one_chip):
    _compile(ops.ensemble_accumulate, _s(one_chip, (SEG, VOCAB)),
             _s(one_chip, (1, SEG, VOCAB)), _s(one_chip, (1,)))


def test_ensemble_accumulate_quant_compiles(one_chip):
    _compile(ops.ensemble_accumulate_quant, _s(one_chip, (SEG, VOCAB)),
             _s(one_chip, (1, SEG, VOCAB), jnp.int8),
             _s(one_chip, (1, SEG)), _s(one_chip, (1,)))


@pytest.mark.parametrize("seq", [128, 2048])
def test_flash_attention_compiles(one_chip, seq):
    """qwen3's head layout: 16 query / 8 KV heads of 128, bf16."""
    q = _s(one_chip, (1, seq, 16, 128), jnp.bfloat16)
    kv = _s(one_chip, (1, seq, 8, 128), jnp.bfloat16)
    _compile(ops.flash_attention, q, kv, kv, causal=True)


def test_decode_attention_compiles(one_chip):
    q = _s(one_chip, (2, 1, 16, 128), jnp.bfloat16)
    kv = _s(one_chip, (2, 4096, 8, 128), jnp.bfloat16)
    _compile(ops.decode_attention, q, kv, kv,
             _s(one_chip, (4096,), jnp.bool_))


def test_ssd_scan_compiles(one_chip):
    """mamba2-1.3b's SSD shape: 64 heads of 64, state 128, chunk 64."""
    b, s, h, p, n = 1, 256, 64, 64, 128
    _compile(ops.ssd_scan, _s(one_chip, (b, s, h, p)),
             _s(one_chip, (b, s, h)), _s(one_chip, (h,)),
             _s(one_chip, (b, s, n)), _s(one_chip, (b, s, n)), chunk=64)


# Mellum2-12B-A2.5B's expert layer at batch 2: 2 x 2048 tokens x 8
# assignments as rows, 16 held experts, hidden 2304, expert width 896
EXPERT_ROWS, HIDDEN, EXPERT_FF, HELD = 2 * 2048 * 8, 2304, 896, 16


@pytest.mark.parametrize("k,n", [(HIDDEN, EXPERT_FF), (EXPERT_FF, HIDDEN)],
                         ids=["gate_up", "down"])
def test_grouped_matmul_compiles(one_chip, k, n):
    _compile(ops.grouped_matmul, _s(one_chip, (EXPERT_ROWS, k)),
             _s(one_chip, (HELD, k, n)), _s(one_chip, (HELD,), jnp.int32))


def test_grouped_expert_layer_unsorts_without_a_scatter(one_chip):
    """The whole grouped layer at 1 x 2048 tokens, 16 of 64 experts held,
    k 8: its output rows return to their tokens by a gather, so no scatter
    of d-wide float32 rows into the (tokens, hidden) output is left."""
    tokens, routed, top_k = 2048, 64, 8
    cfg = types.SimpleNamespace(moe=MoEConfig(
        num_experts=HELD, top_k=top_k, d_ff_expert=EXPERT_FF, impl="grouped",
        routed_experts=routed))
    p = {"router": _s(one_chip, (HIDDEN, routed), jnp.bfloat16),
         "w_gate": _s(one_chip, (HELD, HIDDEN, EXPERT_FF), jnp.bfloat16),
         "w_up": _s(one_chip, (HELD, HIDDEN, EXPERT_FF), jnp.bfloat16),
         "w_down": _s(one_chip, (HELD, EXPERT_FF, HIDDEN), jnp.bfloat16)}
    hlo = _compile(lambda p, x: moe_ffn_grouped(cfg, p, x), p,
                   _s(one_chip, (1, tokens, HIDDEN)))
    assert not re.search(
        rf"= f32\[{tokens},{HIDDEN}\]\{{[^}}]*\}} scatter\(", hlo)
