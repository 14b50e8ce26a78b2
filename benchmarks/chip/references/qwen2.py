"""Plain float32 reference of the Qwen2 family (``model_type`` qwen2 and,
through ``qwen2_moe.py``, qwen2_moe), in straightforward ``jax.numpy``.

It follows the published Hugging Face modelling code: RMSNorm with a
multiplicative gain, rotary embeddings by rotating halves, grouped-query
attention with biases on q, k and v, a SwiGLU MLP, and an untied LM head.
For qwen2_moe the MLP is a softmax router over every expert, the top-k
experts weighted by their router probability (renormalised over the top-k
when the configuration's ``norm_topk_prob`` says so), with no capacity
limit and no dropped token, plus one shared expert behind a sigmoid gate.

It imports nothing of the program under test.  Its weights are drawn from
the seed by ``weights.py`` one layer at a time, and every matmul runs at
``highest`` precision, so no float32 copy of the whole model is ever made.
``mode="fp8"`` is the control: the same forward with every bfloat16
weight and the activation entering its matmul rounded to float8 (e4m3,
one scale per output channel and per token), and the float32 router to
bfloat16.
"""

from __future__ import annotations

import functools
import json
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.weights import (Spec, base_key, global_tensors,
                                     layer_tensors, seed_words)

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256
FP8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> tuple[int, int, int, int]:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h


def weight_specs(cfg: dict) -> tuple[dict[str, Spec], dict[str, Spec]]:
    """(global tensors, per-layer tensors) in ``x @ W`` layout.  The
    scales are the configuration's ``assumed`` init: N(0, 1/fan_in) for
    matmul weights, so activations keep their size through the stack."""
    d, h, k, dh = dims(cfg)
    v = cfg["vocab_size"]
    init = cfg["assumed"]["init"]

    def mat(fan_in: int, *shape: int) -> Spec:
        return Spec(shape, scale=fan_in ** -0.5)

    glob = {"embed_tokens": Spec((v, d), scale=init["embed_std"]),
            "norm": Spec((d,), kind="gain", scale=init["norm_gain_std"]),
            "lm_head": mat(d, d, v)}
    bias = init["qkv_bias_std"]
    layer = {
        "input_layernorm": Spec((d,), kind="gain",
                                scale=init["norm_gain_std"]),
        "q_proj": mat(d, d, h * dh), "q_bias": Spec((h * dh,), scale=bias),
        "k_proj": mat(d, d, k * dh), "k_bias": Spec((k * dh,), scale=bias),
        "v_proj": mat(d, d, k * dh), "v_bias": Spec((k * dh,), scale=bias),
        "o_proj": mat(h * dh, h * dh, d),
        "post_attention_layernorm": Spec((d,), kind="gain",
                                         scale=init["norm_gain_std"]),
    }
    if cfg.get("num_experts"):
        e, fe = cfg["num_experts"], cfg["moe_intermediate_size"]
        fs = cfg["shared_expert_intermediate_size"]
        layer.update({
            "router": Spec((d, e), jnp.float32, scale=d ** -0.5),
            "experts_gate_proj": mat(d, e, d, fe),
            "experts_up_proj": mat(d, e, d, fe),
            "experts_down_proj": mat(fe, e, fe, d),
            "shared_gate_proj": mat(d, d, fs),
            "shared_up_proj": mat(d, d, fs),
            "shared_down_proj": mat(fs, fs, d),
            "shared_expert_gate": mat(d, d, 1),
        })
    else:
        f = cfg["intermediate_size"]
        layer.update({"gate_proj": mat(d, d, f), "up_proj": mat(d, d, f),
                      "down_proj": mat(f, f, d)})
    return glob, layer


# -- precision of the matmuls ----------------------------------------------


def _fp8(a: jax.Array, axis: int) -> jax.Array:
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    a = a.astype(jnp.float32)
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def matmul(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """``x @ w`` in float32.  In the control mode a bfloat16 weight and its
    input go through float8 first; a float32 weight and its input through
    bfloat16."""
    x = x.astype(jnp.float32)
    if mode == "fp8":
        if w.dtype == jnp.float32:
            x = x.astype(jnp.bfloat16).astype(jnp.float32)
            w = w.astype(jnp.bfloat16)
        else:
            x = _fp8(x, axis=-1)
            w = _fp8(w, axis=-2)
    elif mode != "f32":
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


# -- the layer -------------------------------------------------------------


def rms_norm(x: jax.Array, gain: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding of (B, S, heads, dh) at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(np.concatenate([ang, ang], -1)), jnp.float32)
    sin = jnp.asarray(np.sin(np.concatenate([ang, ang], -1)), jnp.float32)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos[None, :, None] + rot * sin[None, :, None]


def attention(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal grouped-query attention, one block of queries at a time."""
    b, s, h, dh = q.shape
    g = h // k.shape[2]
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    qb = min(QUERY_BLOCK, s)
    key_pos = jnp.arange(s)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k,
                        precision=HIGHEST) * dh ** -0.5
        q_pos = i * qb + jnp.arange(qb)
        sc = jnp.where(key_pos[None, :] <= q_pos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(s // qb))  # (nb, B, qb, H, dh)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, dh)


def _moe(w: dict, x: jax.Array, cfg: dict, mode: str) -> jax.Array:
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs = jax.nn.softmax(matmul(xt, w["router"], mode), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    comb = jnp.zeros_like(probs).at[
        jnp.arange(xt.shape[0])[:, None], top_i].set(top_p)

    def expert(y, e):
        hid = (jax.nn.silu(matmul(xt, w["experts_gate_proj"][e], mode))
               * matmul(xt, w["experts_up_proj"][e], mode))
        y = y + comb[:, e, None] * matmul(hid, w["experts_down_proj"][e],
                                          mode)
        return y, None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(xt),
                        jnp.arange(cfg["num_experts"]))
    hid = (jax.nn.silu(matmul(xt, w["shared_gate_proj"], mode))
           * matmul(xt, w["shared_up_proj"], mode))
    gate = jax.nn.sigmoid(matmul(xt, w["shared_expert_gate"], mode))
    y = y + gate * matmul(hid, w["shared_down_proj"], mode)
    return y.reshape(b, s, d)


def layer(w: dict, x: jax.Array, cfg: dict, mode: str) -> jax.Array:
    d, h, k, dh = dims(cfg)
    b, s, _ = x.shape
    eps = cfg["rms_norm_eps"]
    a = rms_norm(x, w["input_layernorm"], eps)
    q = (matmul(a, w["q_proj"], mode) + w["q_bias"]).reshape(b, s, h, dh)
    kk = (matmul(a, w["k_proj"], mode) + w["k_bias"]).reshape(b, s, k, dh)
    vv = (matmul(a, w["v_proj"], mode) + w["v_bias"]).reshape(b, s, k, dh)
    theta = cfg["rope_theta"]
    o = attention(rope(q, theta), rope(kk, theta), vv)
    x = x + matmul(o.reshape(b, s, h * dh), w["o_proj"], mode)
    a = rms_norm(x, w["post_attention_layernorm"], eps)
    if cfg.get("num_experts"):
        return x + _moe(w, a, cfg, mode)
    hid = (jax.nn.silu(matmul(a, w["gate_proj"], mode))
           * matmul(a, w["up_proj"], mode))
    return x + matmul(hid, w["down_proj"], mode)


# -- whole model -------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, mode: str) -> dict[str, Any]:
    cfg = json.loads(cfg_json)
    gspecs, lspecs = weight_specs(cfg)
    return {
        "globals": jax.jit(lambda lo, hi: global_tensors(base_key(lo, hi),
                                                         gspecs)),
        "layer_w": jax.jit(lambda lo, hi, i: layer_tensors(
            base_key(lo, hi), lspecs, i)),
        "embed": jax.jit(lambda e, t: e[t].astype(jnp.float32)),
        "layer": jax.jit(lambda w, x: layer(w, x, cfg, mode)),
        "head": jax.jit(lambda x, n, hw, pos: matmul(
            rms_norm(jnp.take_along_axis(x, pos[..., None], axis=1), n,
                     cfg["rms_norm_eps"]), hw, mode)),
    }


def logits_at(cfg: dict, seed: int, tokens: np.ndarray,
              positions: np.ndarray, mode: str = "f32") -> jax.Array:
    """Float32 logits (B, K, V) at ``positions`` (B, K) of the teacher-
    forced sequences ``tokens`` (B, S), with the weights of ``seed``.

    ``S`` must be a multiple of the query block (or smaller than it).
    Layers run one at a time: each layer's weights are drawn, used on the
    whole batch and dropped.
    """
    prog = _programs(json.dumps(cfg, sort_keys=True), mode)
    lo, hi = seed_words(seed)
    g = prog["globals"](lo, hi)
    x = prog["embed"](g["embed_tokens"], jnp.asarray(tokens, jnp.int32))
    for i in range(cfg["num_hidden_layers"]):
        w = prog["layer_w"](lo, hi, jnp.int32(i))
        x = prog["layer"](w, x)
        del w
    return prog["head"](x, g["norm"], g["lm_head"],
                        jnp.asarray(positions, jnp.int32))
