"""RWKV-6 "Finch": attention-free LM with data-dependent decay (rwkv6-1.6b).

Per layer: a TimeMix block (token-shift ddlerp for r/k/v/w/g, low-rank
data-dependent decay, WKV recurrence with per-head state) and a ChannelMix
block (token-shift, squared-relu FFN).  The WKV recurrence runs through
``repro.kernels.ops.wkv6_scan`` on its XLA ``lax.scan`` path, on every
platform (the Pallas kernel is refused by the v5e compiler).

Decode state per layer: (tm_x (B,D), cm_x (B,D), wkv (B,H,Dh,Dh)) — O(1) in
sequence length, which is why rwkv6 runs the long_500k cell.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import named
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import PSpec, rms_norm, stack_tree

DECAY_LORA = 64


def _heads(cfg: ModelConfig) -> tuple[int, int]:
    dh = 64  # rwkv6 head size
    return cfg.d_model // dh, dh


def time_mix_specs(cfg: ModelConfig) -> dict[str, PSpec]:
    d = cfg.d_model
    h, dh = _heads(cfg)
    return {
        "ln": PSpec((d,), (None,), init="zeros"),
        # token-shift interpolation vectors for r, k, v, w, g
        "mu": PSpec((5, d), (None, None), init="small"),
        "w_r": PSpec((d, d), ("fsdp", "tp")),
        "w_k": PSpec((d, d), ("fsdp", "tp")),
        "w_v": PSpec((d, d), ("fsdp", "tp")),
        "w_g": PSpec((d, d), ("fsdp", "tp")),
        "w_o": PSpec((d, d), ("tp", "fsdp")),
        # data-dependent decay: w = exp(-exp(w0 + tanh(x@a)@b))
        "decay_w0": PSpec((d,), (None,), init="small"),
        "decay_a": PSpec((d, DECAY_LORA), ("fsdp", None)),
        "decay_b": PSpec((DECAY_LORA, d), (None, "fsdp")),
        "bonus_u": PSpec((h, dh), (None, None), init="small"),
        "gn": PSpec((d,), (None,), init="zeros"),  # per-head group norm scale
    }


def channel_mix_specs(cfg: ModelConfig) -> dict[str, PSpec]:
    d, f = cfg.d_model, cfg.d_ff
    return {
        "ln": PSpec((d,), (None,), init="zeros"),
        "mu": PSpec((2, d), (None, None), init="small"),
        "w_k": PSpec((d, f), ("fsdp", "tp")),
        "w_v": PSpec((f, d), ("tp", "fsdp")),
        "w_r": PSpec((d, d), ("fsdp", None)),
    }


def rwkv_specs(cfg: ModelConfig) -> dict[str, Any]:
    d, v = cfg.d_model, cfg.padded_vocab
    layer = {"tm": time_mix_specs(cfg), "cm": channel_mix_specs(cfg)}
    return {
        "embed": PSpec((v, d), ("vocab", "fsdp"), init="small"),
        "ln_in": PSpec((d,), (None,), init="zeros"),
        "layers": stack_tree(layer, cfg.n_layers),
        "ln_f": PSpec((d,), (None,), init="zeros"),
        "head": PSpec((d, v), ("fsdp", "vocab")),
    }


def _shift(x: jax.Array, last: jax.Array | None = None) -> jax.Array:
    """Token shift: previous token's features (zeros / carried state)."""
    if last is None:
        return jnp.pad(x[:, :-1], ((0, 0), (1, 0), (0, 0)))
    return jnp.concatenate([last[:, None, :], x[:, :-1]], axis=1)


def _ddlerp(x: jax.Array, shifted: jax.Array, mu: jax.Array) -> jax.Array:
    return x + (shifted - x) * mu.astype(x.dtype)


def _group_norm(x: jax.Array, scale: jax.Array, h: int, dh: int,
                eps: float) -> jax.Array:
    b, s, d = x.shape
    xf = x.astype(jnp.float32).reshape(b, s, h, dh)
    mu = xf.mean(-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(-1, keepdims=True)
    out = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(b, s, d)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def time_mix(p: dict, x: jax.Array, state: jax.Array,
             last_x: jax.Array | None, cfg: ModelConfig
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (out, new wkv state, new last_x)."""
    h, dh = _heads(cfg)
    b, s, d = x.shape
    xs = _shift(x, last_x)
    xr = _ddlerp(x, xs, p["mu"][0])
    xk = _ddlerp(x, xs, p["mu"][1])
    xv = _ddlerp(x, xs, p["mu"][2])
    xw = _ddlerp(x, xs, p["mu"][3])
    xg = _ddlerp(x, xs, p["mu"][4])
    r = (xr @ p["w_r"]).reshape(b, s, h, dh)
    k = (xk @ p["w_k"]).reshape(b, s, h, dh)
    v = (xv @ p["w_v"]).reshape(b, s, h, dh)
    g = jax.nn.silu((xg @ p["w_g"]).astype(jnp.float32)).astype(x.dtype)
    # Data-dependent decay in log space: w <= 0 guarantees stability.
    lora = jnp.tanh(xw.astype(jnp.float32) @ p["decay_a"].astype(jnp.float32))
    w = -jnp.exp(p["decay_w0"].astype(jnp.float32)
                 + lora @ p["decay_b"].astype(jnp.float32))
    w = w.reshape(b, s, h, dh)
    out, state = ops.wkv6_scan(r, k, v, w.astype(x.dtype), p["bonus_u"], state)
    out = _group_norm(out.reshape(b, s, d), p["gn"], h, dh, cfg.norm_eps)
    out = (out * g) @ p["w_o"]
    return named(out, "batch", "seq", None), state, x[:, -1, :]


def channel_mix(p: dict, x: jax.Array, last_x: jax.Array | None
                ) -> tuple[jax.Array, jax.Array]:
    xs = _shift(x, last_x)
    xk = _ddlerp(x, xs, p["mu"][0])
    xr = _ddlerp(x, xs, p["mu"][1])
    k = jnp.square(jax.nn.relu((xk @ p["w_k"]).astype(jnp.float32)))
    k = named(k.astype(x.dtype), "batch", "seq", "d_ff")
    r = jax.nn.sigmoid((xr @ p["w_r"]).astype(jnp.float32)).astype(x.dtype)
    return r * (k @ p["w_v"]), x[:, -1, :]


def _block(lp: dict, x: jax.Array, wkv: jax.Array,
           tm_x: jax.Array | None, cm_x: jax.Array | None, cfg: ModelConfig
           ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    h = rms_norm(x, lp["tm"]["ln"], cfg.norm_eps)
    a, wkv, tm_x = time_mix(lp["tm"], h, wkv, tm_x, cfg)
    x = x + a
    h = rms_norm(x, lp["cm"]["ln"], cfg.norm_eps)
    m, cm_x = channel_mix(lp["cm"], h, cm_x)
    x = named(x + m, "batch", "seq", None)
    return x, wkv, tm_x, cm_x


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            ctx=None, remat: bool = False,
            train: bool = True) -> tuple[jax.Array, jax.Array]:
    b, s = tokens.shape
    h, dh = _heads(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    x = rms_norm(x, params["ln_in"], cfg.norm_eps)
    x = named(x, "batch", "seq", None)
    wkv0 = jnp.zeros((b, h, dh, dh), jnp.float32)

    def body(x, lp):
        x, _, _, _ = _block(lp, x, wkv0, None, None, cfg)
        return x, None

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = (x @ params["head"]).astype(jnp.float32)
    return named(logits, "batch", "seq", "vocab"), jnp.zeros((), jnp.float32)


def prefill(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            max_len=None, ctx=None) -> tuple[jax.Array, dict]:
    b, s = tokens.shape
    h, dh = _heads(cfg)
    x = jnp.take(params["embed"], tokens, axis=0)
    x = rms_norm(x, params["ln_in"], cfg.norm_eps)
    wkv0 = jnp.zeros((b, h, dh, dh), jnp.float32)

    def body(x, lp):
        x, wkv, tm_x, cm_x = _block(lp, x, wkv0, None, None, cfg)
        return x, (wkv, tm_x, cm_x)

    x, (wkvs, tm_xs, cm_xs) = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x[:, -1:, :], params["ln_f"], cfg.norm_eps)
    logits = (x @ params["head"]).astype(jnp.float32)[:, 0]
    cache = {"wkv": wkvs, "tm_x": tm_xs, "cm_x": cm_xs,
             "pos": jnp.full((), s, jnp.int32)}
    return logits, cache


def decode_step(params: dict, token: jax.Array, cache: dict,
                cfg: ModelConfig) -> tuple[jax.Array, dict]:
    x = jnp.take(params["embed"], token[:, None], axis=0)
    x = rms_norm(x, params["ln_in"], cfg.norm_eps)

    def body(x, xs):
        lp, wkv, tm_x, cm_x = xs
        x, wkv, tm_x, cm_x = _block(lp, x, wkv, tm_x, cm_x, cfg)
        return x, (wkv, tm_x, cm_x)

    x, (wkvs, tm_xs, cm_xs) = jax.lax.scan(
        body, x, (params["layers"], cache["wkv"], cache["tm_x"],
                  cache["cm_x"]))
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    logits = (x @ params["head"]).astype(jnp.float32)[:, 0]
    return logits, {"wkv": wkvs, "tm_x": tm_xs, "cm_x": cm_xs,
                    "pos": cache["pos"] + 1}
