"""Plain reference for h2o-danube (arXiv:2401.16818): a Llama/Mistral-style
decoder with grouped-query attention, rotary positions, a sliding window,
RMSNorm and a SwiGLU MLP, with an untied output head.

This file imports nothing of the program under test.  It defines what
``chipbench.manifest.REFERENCE_API`` names; chiefly:

* :func:`init_params` -- the benchmark's own weight generator.  It draws
  every member's weights from a key on the device, in the layout the
  program takes them (layers stacked on a leading axis; RMSNorm gains
  stored as offsets from 1, drawn as 0, so every gain is 1 as in a freshly
  initialised model).  The harness hands its output to the program, and the
  reference draws the same weights again itself.
* :func:`last_logits` -- the forward pass in plain ``jax.numpy`` and
  float32 at ``Precision.HIGHEST``, returning the last position's logits
  (what the served classifier answers).  ``weights="int8"`` rounds every
  matrix to int8 first: the correctness control.
* :func:`program_config` -- the program's config object for these sizes.
* :func:`rehearsal` -- the size the CPU rehearsal runs, and
  :func:`model_flops_per_row` -- the work ``mfu`` counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import flops

INIT_SCALE = 0.02
_NORMS = ("pre_norm", "mlp_norm", "final_norm")


def shapes(cfg: dict) -> dict:
    """Leaf shapes in the program's layout."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, ff, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]
    layer = {"pre_norm": (n, d), "wq": (n, d, h, hd), "wk": (n, d, kv, hd),
             "wv": (n, d, kv, hd), "wo": (n, h, hd, d), "mlp_norm": (n, d),
             "w_gate": (n, d, ff), "w_up": (n, d, ff), "w_down": (n, ff, d)}
    return {"embed": (v, d), "final_norm": (d,), "head": (d, v),
            "layers": [layer]}


def init_params(key, cfg: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights, N(0, 0.02^2), in ``dtype``.  Traceable: jit it with
    ``out_shardings`` to make them in one call on one chip."""
    leaves = []

    def walk(prefix, node):
        if isinstance(node, dict):
            return {k: walk(f"{prefix}/{k}", v) for k, v in sorted(
                node.items())}
        if isinstance(node, list):
            return [walk(f"{prefix}/{i}", v) for i, v in enumerate(node)]
        leaves.append(prefix)
        k = jax.random.fold_in(key, len(leaves))
        if prefix.rsplit("/", 1)[-1] in _NORMS:
            return jnp.zeros(node, dtype)
        return (jax.random.normal(k, node, jnp.float32)
                * INIT_SCALE).astype(dtype)

    return walk("", shapes(cfg))


def param_bytes(cfg: dict, itemsize: int = 2) -> int:
    total = 0
    for leaf in jax.tree_util.tree_leaves(
            shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)):
        size = 1
        for s in leaf:
            size *= s
        total += size
    return total * itemsize


def rehearsal(cfg: dict) -> dict:
    """The configuration cut to a few layers and a narrow width, for the
    CPU rehearsal only; the cells run it at the published widths."""
    return dict(cfg, num_hidden_layers=2, hidden_size=64,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=128, vocab_size=512,
                # at this size on the CPU the program reads about 3e-8 on
                # both numbers and the int8 control 4e-3 and 1e-2; the limits
                # sit between, as the configuration's do between the chip's
                # readings
                limits={"max_abs_err": 1e-4, "rms_rel_err": 1e-4})


def model_flops_per_row(cfg: dict, seq: int) -> float:
    """A dense decoder's work for one row: ``flops.model_flops_per_row``."""
    return flops.model_flops_per_row(cfg, seq)


def _quantize_int8(w: jax.Array, in_axes: tuple) -> jax.Array:
    """Symmetric int8 with one scale per output channel (max over the input
    axes), returned dequantized in float32."""
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


# input axes of each matrix (after the layer axis is scanned away)
_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_gate": (0,), "w_up": (0,), "w_down": (0,), "head": (0,),
            "embed": (1,)}


def _rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _rope(x, theta):
    """Rotary positions, rotate-half convention.  x: (B, S, H, hd)."""
    hd, s = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("cfg_items", "weights"))
def _last_logits(params, tokens, cfg_items, weights):
    cfg = dict(cfg_items)
    ein = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    window = cfg.get("sliding_window") or 0

    def w(name, x):
        x = x.astype(jnp.float32)
        if weights == "int8" and name in _IN_AXES:
            x = _quantize_int8(x, _IN_AXES[name])
        return x

    s = tokens.shape[1]
    pos = jnp.arange(s)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok &= pos[None, :] > pos[:, None] - window
    bias = jnp.where(ok, 0.0, -1e30).astype(jnp.float32)

    x = jnp.take(w("embed", params["embed"]), tokens, axis=0)

    def layer(x, lp):
        hn = _rms_norm(x, lp["pre_norm"], eps)
        q = _rope(ein("bsd,dhk->bshk", hn, w("wq", lp["wq"])), theta)
        k = _rope(ein("bsd,dhk->bshk", hn, w("wk", lp["wk"])), theta)
        v = ein("bsd,dhk->bshk", hn, w("wv", lp["wv"]))
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        scores = ein("bqhk,bshk->bhqs", q, k).astype(jnp.float32)
        scores = scores * q.shape[-1] ** -0.5 + bias
        probs = jax.nn.softmax(scores, axis=-1)
        att = ein("bhqs,bshk->bqhk", probs, v)
        x = x + ein("bshk,hkd->bsd", att, w("wo", lp["wo"]))
        hn = _rms_norm(x, lp["mlp_norm"], eps)
        g = ein("bsd,df->bsf", hn, w("w_gate", lp["w_gate"]))
        u = ein("bsd,df->bsf", hn, w("w_up", lp["w_up"]))
        x = x + ein("bsf,fd->bsd", jax.nn.silu(g) * u,
                    w("w_down", lp["w_down"]))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"][0])
    x = _rms_norm(x[:, -1], params["final_norm"], eps)
    out = ein("bd,dv->bv", x, w("head", params["head"]))
    return out[:, :cfg["vocab_size"]].astype(jnp.float32)


def last_logits(params, tokens, cfg: dict, *,
                weights: str = "stored") -> jax.Array:
    """Last-position logits (B, vocab): float32 activations, every matmul
    at HIGHEST.  ``weights="int8"`` rounds every matrix to int8 with
    per-output-channel scales first."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "vocab_size",
            "num_hidden_layers", "rms_norm_eps", "rope_theta",
            "sliding_window")
    items = tuple((k, cfg[k]) for k in keys)
    return _last_logits(params, tokens, items, weights)


def program_config(cfg: dict):
    """The program's model config at these sizes."""
    from repro.configs.base import ATTN, SWA, ModelConfig
    return ModelConfig(
        name=cfg["model"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        pattern=(SWA,) if cfg.get("sliding_window") else (ATTN,),
        sliding_window=cfg.get("sliding_window") or 0,
        rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], vocab_pad_to=1,
        source=cfg["source"])
