"""Plain reference for Mellum2-12B-A2.5B (JetBrains, config.json on the
Hugging Face hub): a decoder with grouped-query attention, three windowed
layers for each full one, rotary positions (YaRN on the full layers),
RMSNorm, an untied output head, and in every layer a sparse MLP of routed
SwiGLU experts -- held here as one chip's share of an expert-parallel
deployment (the configuration file's ``deployment``).

This file imports nothing of the program under test outside
:func:`program_config`.  It defines what ``chipbench.manifest.REFERENCE_API``
names; chiefly:

* :func:`init_params` -- the benchmark's own weight generator, in the layout
  the program takes: the layer stack as one dict per position of the layer
  period, each leaf stacked over the period's repeats; RMSNorm gains stored
  as offsets from 1, drawn as 0.
* :func:`last_logits` -- the forward pass in plain ``jax.numpy`` and float32
  at ``Precision.HIGHEST``, one row at a time: the causal mask with the
  window on the windowed layers; YaRN on the full layers; the router over
  every published expert in float32, top-k, renormalised; each held expert's
  SwiGLU computed for every token and weighted by the token's router weight
  for it (zero where the expert is not among its top-k).  Experts this chip
  does not hold add nothing, as in the program.  ``weights="int8"`` rounds
  every matrix to int8 first: the correctness control.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

INIT_SCALE = 0.02
_NORMS = ("pre_norm", "mlp_norm", "final_norm")
_FULL, _WINDOW = "full_attention", "sliding_attention"


def period(cfg: dict) -> list:
    """The layer period: the shortest prefix of ``layer_types`` that
    repeats through the stack."""
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    for p in range(1, len(kinds) + 1):
        if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p):
            return kinds[:p]
    raise ValueError("no layer period")


def routed(cfg: dict) -> int:
    """Experts the router scores: the published count."""
    return cfg["published"]["num_experts"]


def shapes(cfg: dict) -> dict:
    """Leaf shapes in the program's layout."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f, v = cfg["head_dim"], cfg["moe_intermediate_size"], \
        cfg["vocab_size"]
    e = cfg["num_experts"]
    unit = period(cfg)
    n = cfg["num_hidden_layers"] // len(unit)
    layer = {"pre_norm": (n, d), "wq": (n, d, h, hd), "wk": (n, d, kv, hd),
             "wv": (n, d, kv, hd), "wo": (n, h, hd, d), "mlp_norm": (n, d),
             "router": (n, d, routed(cfg)), "w_gate": (n, e, d, f),
             "w_up": (n, e, d, f), "w_down": (n, e, f, d)}
    return {"embed": (v, d), "final_norm": (d,), "head": (d, v),
            "layers": [dict(layer) for _ in unit]}


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
        total += math.prod(leaf)
    return total * itemsize


def rehearsal(cfg: dict) -> dict:
    """The configuration cut to one layer period at a narrow width, for the
    CPU rehearsal only: 2 held experts of 8 routed, 4 per token, and a
    window of 8 that bites at the rehearsal's 16 positions.  The cells run
    it at the published widths."""
    return dict(cfg, num_hidden_layers=4, layer_types=cfg["layer_types"][:4],
                hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                head_dim=16, moe_intermediate_size=32, vocab_size=512,
                num_experts=2, num_experts_per_tok=4, sliding_window=8,
                published=dict(cfg["published"], num_experts=8),
                # at this size on the CPU the program reads about 1e-7 on
                # both numbers and the int8 control 1e-2 and above; the
                # limits sit between, as the configuration's do between the
                # chip's readings
                limits={"max_abs_err": 1e-4, "rms_rel_err": 1e-4})


def attention_pairs(seq: int, window: int = 0) -> int:
    """(query, key) pairs the causal mask allows over ``seq`` positions,
    inside ``window`` where one is given."""
    return sum(min(i + 1, window or seq) for i in range(seq))


def model_flops_per_row(cfg: dict, seq: int) -> float:
    """What one member needs to score one row of ``seq`` tokens, 2 FLOP per
    multiply-add: the q, k, v and o projections and the router over every
    token; the held experts' SwiGLU for the average token, which sends
    ``num_experts_per_tok`` x held / routed of its assignments here; QK^T
    and PV over the pairs each layer's mask allows; the head on the last
    position.  Padding rows, and the rows the mask sets aside, are not
    counted."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, f = cfg["head_dim"], cfg["moe_intermediate_size"]
    experts = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed(cfg)
    per_token = 2.0 * (2 * d * h * hd + 2 * d * kv * hd + d * routed(cfg)
                       + experts * 3 * d * f)
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    pairs = sum(attention_pairs(seq, 0 if k == _FULL
                                else cfg["sliding_window"]) for k in kinds)
    return (seq * per_token * len(kinds) + 4.0 * pairs * h * hd
            + 2.0 * d * cfg["vocab_size"])


def _quantize_int8(w: jax.Array, in_axes: tuple) -> jax.Array:
    """Symmetric int8 with one scale per output channel (max over the input
    axes), returned dequantized in float32."""
    amax = jnp.max(jnp.abs(w), axis=in_axes, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


# input axes of each matrix (after the repeat axis is scanned away)
_IN_AXES = {"wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "router": (0,), "w_gate": (1,), "w_up": (1,), "w_down": (1,),
            "head": (0,), "embed": (1,)}


def _rms_norm(x, w, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def _inv_freq(hd: int, rope: dict) -> jax.Array:
    """Rotary inverse frequencies of one layer kind's ``rope_parameters``:
    the default's, or YaRN's (arXiv:2309.00071 §3.2), interpolated by
    ``factor`` below a linear ramp between the dimensions that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    theta = rope["rope_theta"]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    if rope["rope_type"] == "default":
        return inv
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def dim(turns):
        return hd * math.log(rope["original_max_position_embeddings"]
                             / (turns * 2 * math.pi)) / (2 * math.log(theta))

    lo = max(math.floor(dim(rope["beta_fast"])), 0)
    hi = min(math.ceil(dim(rope["beta_slow"])), hd - 1)
    ramp = jnp.clip((jnp.arange(hd // 2, dtype=jnp.float32) - lo)
                    / (hi - lo if hi > lo else 1e-3), 0.0, 1.0)
    return inv / rope["factor"] * ramp + inv * (1.0 - ramp)


def _rope(x, rope: dict):
    """Rotate-half rotary positions; x: (S, H, hd).  YaRN scales cos and
    sin by its ``attention_factor``."""
    s, hd = x.shape[0], x.shape[-1]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * _inv_freq(hd, rope)
    scale = rope.get("attention_factor", 1.0) \
        if rope["rope_type"] == "yarn" else 1.0
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cfg_json", "weights"))
def _last_logits(params, tokens, cfg_json, weights):
    cfg = json.loads(cfg_json)
    ein = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    eps = cfg["rms_norm_eps"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    top_k, first = cfg["num_experts_per_tok"], cfg["first_held_expert"]
    held = first + jnp.arange(cfg["num_experts"])
    unit = period(cfg)
    s = tokens.shape[1]
    pos = jnp.arange(s)
    causal = pos[None, :] <= pos[:, None]
    bias = {k: jnp.where(causal if k == _FULL else
                         causal & (pos[None, :] > pos[:, None]
                                   - cfg["sliding_window"]),
                         0.0, -1e30).astype(jnp.float32) for k in set(unit)}

    def w(name, x):
        x = x.astype(jnp.float32)
        if weights == "int8" and name in _IN_AXES:
            x = _quantize_int8(x, _IN_AXES[name])
        return x

    def layer(x, lp, kind):
        """x: (S, d) -> (S, d)."""
        rope = cfg["rope_parameters"][kind]
        hn = _rms_norm(x, lp["pre_norm"], eps)
        q = _rope(ein("sd,dhk->shk", hn, w("wq", lp["wq"])), rope)
        k = _rope(ein("sd,dhk->shk", hn, w("wk", lp["wk"])), rope)
        v = ein("sd,dhk->shk", hn, w("wv", lp["wv"]))
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        scores = ein("qhk,shk->hqs", q, k) * q.shape[-1] ** -0.5 + bias[kind]
        att = ein("hqs,shk->qhk", jax.nn.softmax(scores, axis=-1), v)
        x = x + ein("shk,hkd->sd", att, w("wo", lp["wo"]))
        hn = _rms_norm(x, lp["mlp_norm"], eps)
        probs = jax.nn.softmax(ein("sd,de->se", hn, w("router", lp["router"])),
                               axis=-1)
        top, idx = jax.lax.top_k(probs, top_k)
        top = top / top.sum(-1, keepdims=True)
        # each held expert's weight for each token: 0 unless in its top-k
        gate = (top[:, :, None] * (idx[:, :, None] == held)).sum(1)  # (S, E)
        g = ein("sd,edf->esf", hn, w("w_gate", lp["w_gate"]))
        u = ein("sd,edf->esf", hn, w("w_up", lp["w_up"]))
        y = ein("esf,efd->esd", jax.nn.silu(g) * u, w("w_down", lp["w_down"]))
        return x + ein("se,esd->sd", gate, y)

    def row(toks):
        x = jnp.take(w("embed", params["embed"]), toks, axis=0)

        def repeat(x, lps):
            for lp, kind in zip(lps, unit):
                x = layer(x, lp, kind)
            return x, None

        x, _ = jax.lax.scan(repeat, x, params["layers"])
        x = _rms_norm(x[-1], params["final_norm"], eps)
        return ein("d,dv->v", x, w("head", params["head"]))

    return jax.lax.map(row, tokens)[:, :cfg["vocab_size"]]


def last_logits(params, tokens, cfg: dict, *,
                weights: str = "stored") -> jax.Array:
    """Last-position logits (B, vocab): float32 activations, every matmul
    at HIGHEST, one row at a time.  ``weights="int8"`` rounds every matrix
    to int8 with per-output-channel scales first."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "moe_intermediate_size", "vocab_size",
            "num_hidden_layers", "layer_types", "rms_norm_eps",
            "rope_parameters", "sliding_window", "num_experts",
            "num_experts_per_tok", "first_held_expert", "published")
    cfg_json = json.dumps({k: cfg[k] for k in keys}, sort_keys=True)
    return _last_logits(params, tokens, cfg_json, weights)


def program_config(cfg: dict):
    """The program's model config at these sizes."""
    from repro.configs.base import ATTN, SWA, ModelConfig, MoEConfig, \
        YarnConfig
    ropes = cfg["rope_parameters"]
    if ropes[_FULL]["rope_theta"] != ropes[_WINDOW]["rope_theta"]:
        raise ValueError("the program takes one rope_theta for every layer")
    full = ropes[_FULL]
    return ModelConfig(
        name=cfg["model"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=0,                                 # every MLP is sparse
        vocab_size=cfg["vocab_size"],
        pattern=tuple(ATTN if k == _FULL else SWA for k in period(cfg)),
        sliding_window=cfg["sliding_window"],
        rope_theta=float(ropes[_WINDOW]["rope_theta"]),
        yarn=YarnConfig(
            factor=float(full["factor"]),
            original_max_position=int(
                full["original_max_position_embeddings"]),
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        norm_eps=cfg["rms_norm_eps"],
        moe=MoEConfig(num_experts=cfg["num_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_ff_expert=cfg["moe_intermediate_size"],
                      impl="grouped", routed_experts=routed(cfg),
                      first_expert=cfg["first_held_expert"]),
        tie_embeddings=cfg["tie_word_embeddings"], vocab_pad_to=1,
        source=cfg["source"])
