"""Decoder assembly for all six architecture families.

The layer stack is ``repeats`` copies of the config's pattern unit; parameters
and decode caches carry a leading ``repeats`` dim and the stack is a single
``lax.scan`` over it (compile size independent of depth).

Public API:
    init_params(rng, cfg)                         -> param pytree
    forward(params, cfg, tokens, frontend=None)   -> (logits, aux_loss)
    last_logits(params, cfg, tokens, ...)         -> (last-position logits,
                                                      expert rows computed)
    prefill(params, cfg, tokens, max_len, ...)    -> (logits, cache)
    decode_step(params, cfg, cache, token, pos)   -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN, CROSS, HYBRID, SSM, SWA, ModelConfig
from repro.models import attention as attn_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import embed, rms_norm, swiglu, unembed
from repro.models.moe import moe_ffn


# --------------------------------------------------------------------------
# Parameter construction
# --------------------------------------------------------------------------
def _layer_param_shapes(cfg: ModelConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    d, hd, h, kv = cfg.d_model, cfg.hd, cfg.num_heads, cfg.num_kv_heads
    shapes: Dict[str, Tuple[int, ...]] = {"pre_norm": (d,)}
    if kind in (ATTN, SWA, CROSS, HYBRID):
        kv_src = cfg.fdim if kind == CROSS else d
        shapes.update(wq=(d, h, hd), wk=(kv_src, kv, hd), wv=(kv_src, kv, hd),
                      wo=(h, hd, d))
        if cfg.qk_norm:
            shapes.update(q_norm=(hd,), k_norm=(hd,))
    if kind in (SSM, HYBRID):
        s, di, nh = cfg.ssm, cfg.d_inner, cfg.ssm_heads
        shapes.update(in_proj=(d, 2 * di + 2 * s.d_state + nh),
                      conv_w=(s.d_conv, di + 2 * s.d_state),
                      dt_bias=(nh,), A_log=(nh,), D=(nh,),
                      norm=(di,), out_proj=(di, d))
    if cfg.moe is not None:
        m = cfg.moe
        shapes.update(mlp_norm=(d,), router=(d, m.router_width),
                      w_gate=(m.num_experts, d, m.d_ff_expert),
                      w_up=(m.num_experts, d, m.d_ff_expert),
                      w_down=(m.num_experts, m.d_ff_expert, d))
        if m.shared_expert:
            shapes.update(ws_gate=(d, m.d_ff_shared), ws_up=(d, m.d_ff_shared),
                          ws_down=(m.d_ff_shared, d))
    elif cfg.d_ff > 0:
        shapes.update(mlp_norm=(d,), w_gate=(d, cfg.d_ff), w_up=(d, cfg.d_ff),
                      w_down=(cfg.d_ff, d))
    return shapes


def param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    """Full parameter pytree of shapes (leaves: (shape, dtype-agnostic))."""
    vp, d = cfg.padded_vocab, cfg.d_model
    tree: Dict[str, Any] = {"embed": (vp, d), "final_norm": (d,)}
    if not cfg.tie_embeddings:
        tree["head"] = (d, vp)
    tree["layers"] = [
        {k: (cfg.repeats,) + v for k, v in _layer_param_shapes(cfg, kind).items()}
        for kind in cfg.pattern
    ]
    return tree


_INIT_SCALE = 0.02
_ZERO_INIT = ("pre_norm", "mlp_norm", "q_norm", "k_norm", "final_norm", "norm")


def init_params(rng: jax.Array, cfg: ModelConfig, dtype=jnp.float32):
    """Materialize parameters (used for reduced configs; full configs are
    lowered from ShapeDtypeStructs only)."""
    shapes = param_shapes(cfg)
    counter = [0]

    def make(path: str, shape):
        counter[0] += 1
        key = jax.random.fold_in(rng, counter[0])
        name = path.split("/")[-1]
        if name in _ZERO_INIT:
            return jnp.zeros(shape, dtype)
        if name == "dt_bias":
            # init so softplus(dt_bias) spans [1e-3, 1e-1] (mamba2 default)
            u = jax.random.uniform(key, shape, jnp.float32)
            dt = jnp.exp(u * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
        if name == "A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              minval=1.0, maxval=16.0)).astype(dtype)
        if name == "D":
            return jnp.ones(shape, dtype)
        return (jax.random.normal(key, shape, jnp.float32) * _INIT_SCALE).astype(dtype)

    def build(prefix, node):
        if isinstance(node, dict):
            return {k: build(f"{prefix}/{k}", v) for k, v in node.items()}
        if isinstance(node, list):
            return [build(f"{prefix}/{i}", v) for i, v in enumerate(node)]
        return make(prefix, node)

    return build("", shapes)


def param_struct(cfg: ModelConfig, dtype=jnp.bfloat16):
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation)."""
    def build(node):
        if isinstance(node, dict):
            return {k: build(v) for k, v in node.items()}
        if isinstance(node, list):
            return [build(v) for v in node]
        return jax.ShapeDtypeStruct(node, dtype)
    return build(param_shapes(cfg))


# --------------------------------------------------------------------------
# Full-sequence forward (training / benchmark-mode serving)
# --------------------------------------------------------------------------
def _apply_mlp(cfg: ModelConfig, lp, x):
    """-> (x, aux_loss, expert rows the grouped layer computed)."""
    if cfg.moe is not None:
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        out, aux, rows = moe_ffn(cfg, lp, h)
        return x + out, aux, rows
    if cfg.d_ff > 0:
        h = rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
        return x + swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]), 0.0, 0
    return x, 0.0, 0


def _seq_constraint(x):
    """§Perf variant "seq_par": keep full-sequence activations sequence-sharded
    over the "model" axis between layers (Megatron-SP).  GSPMD then lowers the
    TP boundary as reduce-scatter + all-gather instead of full all-reduce."""
    from repro import runtime_flags
    mesh = runtime_flags.SHARDING_OPTS.get("seq_parallel")
    if mesh is None or x.ndim != 3 or x.shape[1] % mesh.shape["model"] != 0:
        return x
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch.mesh import batch_axes
    bax = batch_axes(mesh)
    bax = bax if len(bax) > 1 else (bax[0] if bax else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(bax, "model", None)))


def _apply_layer(cfg: ModelConfig, kind: str, lp, x, positions, frontend,
                 use_kernel: bool):
    aux = 0.0
    x = _seq_constraint(x)
    h = rms_norm(x, lp["pre_norm"], cfg.norm_eps)
    if kind == ATTN:
        x = x + attn_mod.self_attention(cfg, lp, h, positions, window=0,
                                        use_kernel=use_kernel, yarn=cfg.yarn)
    elif kind == SWA:
        x = x + attn_mod.self_attention(cfg, lp, h, positions,
                                        window=cfg.sliding_window,
                                        use_kernel=use_kernel)
    elif kind == CROSS:
        x = x + attn_mod.cross_attention(cfg, lp, h, frontend,
                                         use_kernel=use_kernel)
    elif kind == SSM:
        x = x + ssm_mod.ssm_mixer(cfg, lp, h, use_kernel=use_kernel)
    elif kind == HYBRID:
        a = attn_mod.self_attention(cfg, lp, h, positions,
                                    window=cfg.sliding_window,
                                    use_kernel=use_kernel)
        m = ssm_mod.ssm_mixer(cfg, lp, h, use_kernel=use_kernel)
        x = x + 0.5 * (a + m)
    else:
        raise ValueError(kind)
    x, aux2, rows = _apply_mlp(cfg, lp, x)
    return x, aux + aux2, rows


def _layers(params, cfg: ModelConfig, tokens: jax.Array,
            frontend: Optional[jax.Array], use_kernel: bool, remat: bool):
    """Embedding and layer stack: tokens (B,S) -> (final hidden states
    (B,S,D) before the final norm, aux_loss, expert rows computed)."""
    x = embed(tokens, params["embed"], cfg.embed_scale)
    positions = jnp.arange(tokens.shape[1])

    def unit_body(carry, unit_params):
        x, aux, rows = carry
        for i, kind in enumerate(cfg.pattern):
            x, a, r = _apply_layer(cfg, kind, unit_params[i], x, positions,
                                   frontend, use_kernel)
            aux, rows = aux + a, rows + r
        return (x, aux, rows), None

    from repro import runtime_flags
    if remat:
        policy = None
        if runtime_flags.SHARDING_OPTS.get("remat_policy") == "dots":
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(unit_body, policy=policy)
    else:
        body = unit_body
    (x, aux, rows), _ = jax.lax.scan(
        body, (x, jnp.float32(0.0), jnp.int32(0)), params["layers"],
        unroll=runtime_flags.scan_unroll())
    return x, aux, rows


def _head(params, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return unembed(x, params["embed"] if cfg.tie_embeddings else params["head"],
                   cfg.tie_embeddings)


def forward(params, cfg: ModelConfig, tokens: jax.Array,
            frontend: Optional[jax.Array] = None, *, use_kernel: bool = False,
            remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B,S) int32 -> (logits (B,S,Vpad), aux_loss)."""
    x, aux, _ = _layers(params, cfg, tokens, frontend, use_kernel, remat)
    return _head(params, cfg, x), aux


def last_logits(params, cfg: ModelConfig, tokens: jax.Array,
                frontend: Optional[jax.Array] = None, *,
                use_kernel: bool = False) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B,S) int32 -> (logits of the last position (B,Vpad), the
    (token, expert) rows the grouped expert layers computed, int32): the
    final norm and the head run on the last position only."""
    x, _, rows = _layers(params, cfg, tokens, frontend, use_kernel, False)
    return _head(params, cfg, x[:, -1]), rows


# --------------------------------------------------------------------------
# Prefill: forward + cache materialization
# --------------------------------------------------------------------------
def _ring_fill(k: jax.Array, L: int) -> jax.Array:
    """Place the last min(S,L) timesteps of k (B,S,...) into an L-slot ring."""
    b, s = k.shape[0], k.shape[1]
    take = min(s, L)
    tail = k[:, s - take:]
    slots = (jnp.arange(take) + (s - take)) % L
    buf = jnp.zeros((b, L) + k.shape[2:], k.dtype)
    return buf.at[:, slots].set(tail)


def _prefill_layer(cfg: ModelConfig, kind: str, lp, x, positions, frontend,
                   max_len: int, use_kernel: bool,
                   quantize_cache: bool = False):
    """Returns (x_out, cache_entry)."""
    h = rms_norm(x, lp["pre_norm"], cfg.norm_eps)
    entry: Dict[str, jax.Array] = {}
    if kind in (ATTN, SWA, HYBRID):
        window = 0 if kind == ATTN else cfg.sliding_window
        q, k, v = attn_mod.project_qkv(cfg, lp, h)
        from repro.models.layers import apply_rope
        yarn = cfg.yarn if kind == ATTN else None
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
        s = x.shape[1]
        if s <= attn_mod._DENSE_MAX:
            out = attn_mod.dense_attention(q, k, v, positions, positions,
                                           causal=True, window=window)
        else:
            out = attn_mod.chunked_attention(q, k, v, positions, positions,
                                             causal=True, window=window)
        a_out = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
        L = max_len if kind == ATTN else min(max_len, cfg.sliding_window)
        if kind == ATTN:
            pad = max_len - k.shape[1]
            entry["k"] = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            entry["v"] = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        else:
            entry["k"], entry["v"] = _ring_fill(k, L), _ring_fill(v, L)
        if quantize_cache:
            from repro.models.cache import quantize_kv
            entry["k"], entry["k_scale"] = quantize_kv(entry["k"])
            entry["v"], entry["v_scale"] = quantize_kv(entry["v"])
    if kind == CROSS:
        q, k, v = attn_mod.project_qkv(cfg, lp, h, kv_src=frontend)
        qp, kp = positions, jnp.arange(frontend.shape[1])
        out = attn_mod.dense_attention(q, k, v, qp, kp, causal=False) \
            if max(x.shape[1], frontend.shape[1]) <= attn_mod._DENSE_MAX else \
            attn_mod.chunked_attention(q, k, v, qp, kp, causal=False)
        a_out = jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
        entry["k"], entry["v"] = k, v
        if quantize_cache:
            from repro.models.cache import quantize_kv
            entry["k"], entry["k_scale"] = quantize_kv(k)
            entry["v"], entry["v_scale"] = quantize_kv(v)
    if kind in (SSM, HYBRID):
        m_out, h_state, conv_tail = ssm_mod.ssm_mixer(cfg, lp, h,
                                                      use_kernel=use_kernel,
                                                      return_state=True)
        entry["h"], entry["conv"] = h_state, conv_tail
    # combine mixer outputs
    if kind in (ATTN, SWA, CROSS):
        x = x + a_out
    elif kind == SSM:
        x = x + m_out
    elif kind == HYBRID:
        x = x + 0.5 * (a_out + m_out)
    x, _, _ = _apply_mlp(cfg, lp, x)
    return x, entry


def prefill(params, cfg: ModelConfig, tokens: jax.Array, max_len: int,
            frontend: Optional[jax.Array] = None, *,
            use_kernel: bool = False,
            quantize_cache: bool = False) -> Tuple[jax.Array, Any]:
    """Run the prompt, return (last-token logits (B,Vpad), cache).

    ``quantize_cache``: store KV as int8 + per-slot scales (decode must then
    run the dequantizing path — automatic, keyed off the cache contents)."""
    x = embed(tokens, params["embed"], cfg.embed_scale)
    positions = jnp.arange(tokens.shape[1])

    def unit_body(x, unit_params):
        entries = []
        for i, kind in enumerate(cfg.pattern):
            x, e = _prefill_layer(cfg, kind, unit_params[i], x, positions,
                                  frontend, max_len, use_kernel,
                                  quantize_cache)
            entries.append(e)
        return x, entries

    from repro import runtime_flags
    x, cache_layers = jax.lax.scan(unit_body, x, params["layers"],
                                   unroll=runtime_flags.scan_unroll())
    return _head(params, cfg, x[:, -1]), {"layers": cache_layers}


# --------------------------------------------------------------------------
# Decode step: one token against the cache
# --------------------------------------------------------------------------
def _decode_layer(cfg: ModelConfig, kind: str, lp, entry, x, pos,
                  use_kernel: bool):
    h = rms_norm(x, lp["pre_norm"], cfg.norm_eps)
    new_entry = dict(entry)
    if kind in (ATTN, SWA):
        window = 0 if kind == ATTN else cfg.sliding_window
        yarn = cfg.yarn if kind == ATTN else None
        if "k_scale" in entry:       # int8-quantized cache
            (a_out, new_entry["k"], new_entry["v"], new_entry["k_scale"],
             new_entry["v_scale"]) = attn_mod.decode_attention(
                cfg, lp, h, entry["k"], entry["v"], pos, window=window,
                use_kernel=use_kernel, k_scale=entry["k_scale"],
                v_scale=entry["v_scale"], yarn=yarn)
        else:
            a_out, new_entry["k"], new_entry["v"] = attn_mod.decode_attention(
                cfg, lp, h, entry["k"], entry["v"], pos, window=window,
                use_kernel=use_kernel, yarn=yarn)
        x = x + a_out
    elif kind == CROSS:
        q, _, _ = attn_mod.project_qkv(
            cfg, lp, h, kv_src=jnp.zeros((x.shape[0], 1, cfg.fdim), x.dtype))
        kc, vc = entry["k"], entry["v"]
        if "k_scale" in entry:
            from repro.models.cache import dequantize_kv
            kc = dequantize_kv(kc, entry["k_scale"], h.dtype)
            vc = dequantize_kv(vc, entry["v_scale"], h.dtype)
        out = attn_mod.dense_attention(
            q, kc, vc, jnp.arange(1),
            jnp.arange(kc.shape[1]), causal=False)
        x = x + jnp.einsum("bshk,hkd->bsd", out, lp["wo"])
    elif kind == SSM:
        m_out, new_entry["h"], new_entry["conv"] = ssm_mod.ssm_decode_step(
            cfg, lp, h, entry["h"], entry["conv"])
        x = x + m_out
    elif kind == HYBRID:
        if "k_scale" in entry:       # int8-quantized cache
            (a_out, new_entry["k"], new_entry["v"], new_entry["k_scale"],
             new_entry["v_scale"]) = attn_mod.decode_attention(
                cfg, lp, h, entry["k"], entry["v"], pos,
                window=cfg.sliding_window, use_kernel=use_kernel,
                k_scale=entry["k_scale"], v_scale=entry["v_scale"])
        else:
            a_out, new_entry["k"], new_entry["v"] = attn_mod.decode_attention(
                cfg, lp, h, entry["k"], entry["v"], pos,
                window=cfg.sliding_window, use_kernel=use_kernel)
        m_out, new_entry["h"], new_entry["conv"] = ssm_mod.ssm_decode_step(
            cfg, lp, h, entry["h"], entry["conv"])
        x = x + 0.5 * (a_out + m_out)
    x, _, _ = _apply_mlp(cfg, lp, x)
    return x, new_entry


def decode_step(params, cfg: ModelConfig, cache, token: jax.Array, pos,
                *, use_kernel: bool = False) -> Tuple[jax.Array, Any]:
    """token: (B,1) int32, pos: scalar int32 -> (logits (B,Vpad), new cache)."""
    x = embed(token, params["embed"], cfg.embed_scale)

    def unit_body(x, xs):
        unit_params, unit_cache = xs
        new_entries = []
        for i, kind in enumerate(cfg.pattern):
            x, e = _decode_layer(cfg, kind, unit_params[i], unit_cache[i], x,
                                 pos, use_kernel)
            new_entries.append(e)
        return x, new_entries

    from repro import runtime_flags
    x, new_layers = jax.lax.scan(unit_body, x, (params["layers"], cache["layers"]),
                                 unroll=runtime_flags.scan_unroll())
    return _head(params, cfg, x[:, 0]), {"layers": new_layers}


# --------------------------------------------------------------------------
# Convenience object used by serving / examples
# --------------------------------------------------------------------------
class Model:
    """Thin functional wrapper binding a config to the apply functions."""

    def __init__(self, cfg: ModelConfig, use_kernel: bool = False):
        self.cfg = cfg
        self.use_kernel = use_kernel

    def init(self, rng, dtype=jnp.float32):
        return init_params(rng, self.cfg, dtype)

    def __call__(self, params, tokens, frontend=None):
        return forward(params, self.cfg, tokens, frontend,
                       use_kernel=self.use_kernel)

    def forward_fn(self):
        return functools.partial(forward, cfg=self.cfg, use_kernel=self.use_kernel)
