"""Decoder-only transformer stack: dense / MoE / VLM families.

One implementation covers qwen2-7b, gemma3-27b, starcoder2-15b,
qwen1.5-110b, mixtral-8x7b, qwen2-moe-a2.7b and llama-3.2-vision-11b:

* **scan-over-layers** keeps HLO size O(1) in depth (512-device compiles);
* **local:global interleave** (gemma3): one uniform layer stack with a
  per-layer ``is_global`` flag; ``lax.cond`` selects windowed vs. full
  attention.  Decode uses a *dual cache*: rolled (B, W, K, Dh) buffers for
  every layer (xs of the scan) plus full-length caches for the few global
  layers (carry, indexed by a per-layer global-slot);
* **sliding-window everywhere** (mixtral): single rolled cache of size W;
* **cross-attention interleave** (llama-vision): self layers grouped, one
  gated cross-attn layer after every ``cross_attn_every`` self layers.

Simplifications recorded in DESIGN.md: RMSNorm for all archs (starcoder2
ships LayerNorm), no QK-norm (gemma3), single rope base.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import named
from repro.models import attention as attn
from repro.models.config import ModelConfig
from repro.models.layers import (PSpec, mlp_apply, mlp_specs, rms_norm,
                                 stack_tree)
from repro.models.moe import moe_apply, moe_specs


# --------------------------------------------------------------------------
# Specs
# --------------------------------------------------------------------------


def block_specs(cfg: ModelConfig) -> dict[str, Any]:
    d = cfg.d_model
    s: dict[str, Any] = {
        "ln1": PSpec((d,), (None,), init="zeros"),
        "attn": attn.attn_specs(cfg),
        "ln2": PSpec((d,), (None,), init="zeros"),
    }
    if cfg.family == "moe":
        s["moe"] = moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs(d, cfg.d_ff, cfg.mlp)
    return s


def cross_block_specs(cfg: ModelConfig) -> dict[str, Any]:
    d = cfg.d_model
    return {
        "ln1": PSpec((d,), (None,), init="zeros"),
        "attn": attn.attn_specs(cfg, cross=True),
        "gate_attn": PSpec((), (), init="zeros"),
        "ln2": PSpec((d,), (None,), init="zeros"),
        "mlp": mlp_specs(d, cfg.d_ff, cfg.mlp),
        "gate_mlp": PSpec((), (), init="zeros"),
    }


def decoder_specs(cfg: ModelConfig) -> dict[str, Any]:
    d, v, l = cfg.d_model, cfg.padded_vocab, cfg.n_layers
    specs: dict[str, Any] = {
        "embed": PSpec((v, d), ("vocab", "fsdp"), init="small"),
        "ln_f": PSpec((d,), (None,), init="zeros"),
        "layers": stack_tree(block_specs(cfg), l),
    }
    if not cfg.tie_embeddings:
        specs["head"] = PSpec((d, v), ("fsdp", "vocab"))
    if cfg.family == "vlm":
        if l % cfg.cross_attn_every:
            raise ValueError("n_layers must divide cross_attn_every groups")
        g = l // cfg.cross_attn_every
        specs["cross_layers"] = stack_tree(cross_block_specs(cfg), g)
    return specs


# --------------------------------------------------------------------------
# Blocks
# --------------------------------------------------------------------------


def _ffn(lp: dict, x: jax.Array, cfg: ModelConfig, train: bool
         ) -> tuple[jax.Array, jax.Array]:
    if cfg.family == "moe":
        cf = cfg.moe_cf_train if train else cfg.moe_cf_eval
        return moe_apply(lp["moe"], x, cfg, capacity_factor=cf)
    return mlp_apply(lp["mlp"], x, cfg.mlp), jnp.zeros((), jnp.float32)


def block_full(lp: dict, x: jax.Array, cfg: ModelConfig, *,
               positions: jax.Array, window: Optional[int],
               train: bool = True
               ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Full-sequence block. Returns (x, k, v, aux_loss)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, k, v = attn.attn_full(lp["attn"], h, cfg, positions=positions,
                             window=window)
    x = named(x + a, "batch", "seq", None)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, aux = _ffn(lp, h, cfg, train)
    x = named(x + m, "batch", "seq", None)
    return x, k, v, aux


def block_decode(lp: dict, x: jax.Array, kc: jax.Array, vc: jax.Array,
                 pos: jax.Array, cfg: ModelConfig, *, rolled: bool,
                 window: Optional[int]
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kc, vc = attn.attn_decode(lp["attn"], h, kc, vc, pos, cfg,
                                 rolled=rolled, window=window)
    x = named(x + a, "batch", "seq", None)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, _ = _ffn(lp, h, cfg, train=False)
    return named(x + m, "batch", "seq", None), kc, vc


def block_decode_paged(lp: dict, x: jax.Array, kc: jax.Array, vc: jax.Array,
                       block_tables: jax.Array, pos: jax.Array,
                       cfg: ModelConfig,
                       active: Optional[jax.Array] = None,
                       layer: Optional[jax.Array] = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """block_decode against one layer's paged KV blocks (or layer
    ``layer`` of the stacked pools)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kc, vc = attn.attn_decode_paged(lp["attn"], h, kc, vc,
                                       block_tables, pos, cfg, active, layer)
    x = named(x + a, "batch", "seq", None)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, _ = _ffn(lp, h, cfg, train=False)
    return named(x + m, "batch", "seq", None), kc, vc


def block_decode_paged_quant(lp: dict, x: jax.Array, kc, vc, ksc, vsc,
                             block_tables: jax.Array, pos: jax.Array,
                             cfg: ModelConfig,
                             active: Optional[jax.Array] = None,
                             layer: Optional[jax.Array] = None):
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kc, vc, ksc, vsc = attn.attn_decode_paged_quant(
        lp["attn"], h, kc, vc, ksc, vsc, block_tables, pos, cfg, active,
        layer)
    x = named(x + a, "batch", "seq", None)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, _ = _ffn(lp, h, cfg, train=False)
    return named(x + m, "batch", "seq", None), kc, vc, ksc, vsc


def block_decode_quant(lp: dict, x: jax.Array, kc, vc, ksc, vsc,
                       pos: jax.Array, cfg: ModelConfig):
    """block_decode against int8 caches (§Perf D)."""
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kc, vc, ksc, vsc = attn.attn_decode_quant(lp["attn"], h, kc, vc,
                                                 ksc, vsc, pos, cfg)
    x = named(x + a, "batch", "seq", None)
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, _ = _ffn(lp, h, cfg, train=False)
    return named(x + m, "batch", "seq", None), kc, vc, ksc, vsc


def cross_block_full(lp: dict, x: jax.Array, ctx: jax.Array,
                     cfg: ModelConfig
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Gated cross-attention block (llama-3.2-vision style).

    Returns (x, ck, cv) — the projected context cache for decode reuse.
    """
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    ck, cv = attn.context_kv(lp["attn"], ctx, cfg)
    a = attn.cross_attn_full(lp["attn"], h, (ck, cv), cfg)
    x = x + jnp.tanh(lp["gate_attn"].astype(jnp.float32)).astype(x.dtype) * a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m = mlp_apply(lp["mlp"], h, cfg.mlp)
    x = x + jnp.tanh(lp["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * m
    return x, ck, cv


def cross_block_decode(lp: dict, x: jax.Array, ck: jax.Array, cv: jax.Array,
                       cfg: ModelConfig) -> jax.Array:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a = attn.cross_attn_decode(lp["attn"], h, ck, cv, cfg)
    x = x + jnp.tanh(lp["gate_attn"].astype(jnp.float32)).astype(x.dtype) * a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m = mlp_apply(lp["mlp"], h, cfg.mlp)
    return x + jnp.tanh(lp["gate_mlp"].astype(jnp.float32)).astype(x.dtype) * m


# --------------------------------------------------------------------------
# Embedding / head
# --------------------------------------------------------------------------


def embed_tokens(params: dict, tokens: jax.Array, cfg: ModelConfig
                 ) -> jax.Array:
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return named(x, "batch", "seq", None)


def lm_head(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = rms_norm(x, params["ln_f"], cfg.norm_eps)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = (x @ w).astype(jnp.float32)
    return named(logits, "batch", "seq", "vocab")


# --------------------------------------------------------------------------
# Layer-pattern helpers
# --------------------------------------------------------------------------


def _layer_flags(cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """(is_global (L,), global_slot (L,)) for local:global interleaves."""
    flags = [cfg.is_global_layer(i) for i in range(cfg.n_layers)]
    slots, c = [], 0
    for f in flags:
        slots.append(c)
        c += int(f)
    return jnp.asarray(flags), jnp.asarray(slots, jnp.int32)


def n_global_layers(cfg: ModelConfig) -> int:
    return sum(cfg.is_global_layer(i) for i in range(cfg.n_layers))


def _dual(cfg: ModelConfig) -> bool:
    return cfg.local_global_ratio > 0 and cfg.sliding_window is not None


def local_cache_len(cfg: ModelConfig, max_len: int) -> int:
    w = cfg.sliding_window
    return min(w, max_len) if w else max_len


# --------------------------------------------------------------------------
# Forward (training) — logits over the full sequence
# --------------------------------------------------------------------------


def forward(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            ctx: Optional[jax.Array] = None, remat: bool = False,
            train: bool = True) -> tuple[jax.Array, jax.Array]:
    """Returns (logits (B,S,V) fp32, moe aux loss)."""
    b, s = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.arange(s)

    def self_body(x, lp, flag):
        if _dual(cfg):
            def global_fn(args):
                lp_, x_ = args
                xo, _, _, aux = block_full(lp_, x_, cfg, positions=positions,
                                           window=None, train=train)
                return xo, aux

            def local_fn(args):
                lp_, x_ = args
                xo, _, _, aux = block_full(lp_, x_, cfg, positions=positions,
                                           window=cfg.sliding_window,
                                           train=train)
                return xo, aux

            x, aux = jax.lax.cond(flag, global_fn, local_fn, (lp, x))
        else:
            x, _, _, aux = block_full(lp, x, cfg, positions=positions,
                                      window=cfg.sliding_window, train=train)
        return x, aux

    if remat:
        self_body = jax.checkpoint(
            self_body, policy=jax.checkpoint_policies.nothing_saveable)

    flags, _ = _layer_flags(cfg)

    if cfg.family == "vlm":
        assert ctx is not None, "vlm forward needs context embeddings"
        every = cfg.cross_attn_every
        g = cfg.n_layers // every
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(g, every, *a.shape[1:]), params["layers"])

        def group_body(carry, xs):
            x, aux = carry
            glp, clp = xs

            def inner(carry2, lp):
                x2, aux2 = carry2
                x2, a2 = self_body(x2, lp, jnp.asarray(True))
                return (x2, aux2 + a2), None

            (x, aux), _ = jax.lax.scan(inner, (x, aux), glp)
            x, _, _ = cross_block_full(clp, x, ctx, cfg)
            return (x, aux), None

        (x, aux), _ = jax.lax.scan(
            group_body, (x, jnp.zeros((), jnp.float32)),
            (grouped, params["cross_layers"]))
    else:
        def body(carry, xs):
            x, aux = carry
            lp, flag = xs
            x, a = self_body(x, lp, flag)
            return (x, aux + a), None

        (x, aux), _ = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), (params["layers"], flags))

    return lm_head(params, x, cfg), aux


# --------------------------------------------------------------------------
# Prefill — forward + emit decode caches
# --------------------------------------------------------------------------


def _windowed_cache(k: jax.Array, w: int, max_len: int) -> jax.Array:
    """Extract a rolled (B, C, K, Dh) cache from full-seq k (B, S, K, Dh)."""
    b, s, kv, dh = k.shape
    c = min(w, max_len)
    if s <= c:
        out = jnp.zeros((b, c, kv, dh), k.dtype)
        return jax.lax.dynamic_update_slice(out, k, (0, 0, 0, 0))
    last = jax.lax.dynamic_slice_in_dim(k, s - c, c, axis=1)
    # slot of position p is p % c; positions [s-c, s) -> roll by s % c.
    return jnp.roll(last, shift=s % c, axis=1)


def _full_cache(k: jax.Array, max_len: int) -> jax.Array:
    b, s, kv, dh = k.shape
    if s == max_len:
        return k
    out = jnp.zeros((b, max_len, kv, dh), k.dtype)
    return jax.lax.dynamic_update_slice(out, k, (0, 0, 0, 0))


def prefill(params: dict, tokens: jax.Array, cfg: ModelConfig, *,
            max_len: Optional[int] = None, ctx: Optional[jax.Array] = None,
            length: Optional[jax.Array] = None) -> tuple[jax.Array, dict]:
    """Run the prompt; returns (last-position logits (B,V), cache dict).

    ``length`` (traced scalar) enables *length-masked* prefill for bucketed
    padding: ``tokens`` may be right-padded beyond the true prompt length,
    logits are read at position ``length - 1`` and the cache position is set
    to ``length``.  Pad rows write garbage K/V beyond ``length``, but decode
    masks the cache at ``pos + 1`` and overwrites those rows token by token,
    so they are never attended.  Only full (non-windowed) caches support
    this: a rolled sliding-window cache folds pad rows into real ones.
    """
    b, s = tokens.shape
    max_len = max_len or s
    if length is not None and (cfg.family == "vlm"
                               or cfg.sliding_window is not None):
        raise NotImplementedError(
            "length-masked prefill requires full (non-windowed) caches")
    x = embed_tokens(params, tokens, cfg)
    positions = jnp.arange(s)
    flags, gslots = _layer_flags(cfg)
    dual = _dual(cfg)
    w = cfg.sliding_window

    if cfg.family == "vlm":
        assert ctx is not None
        every = cfg.cross_attn_every
        g = cfg.n_layers // every
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(g, every, *a.shape[1:]), params["layers"])

        def group_body(x, xs):
            glp, clp = xs

            def inner(x2, lp):
                x2, k, v, _ = block_full(lp, x2, cfg, positions=positions,
                                         window=None, train=False)
                return x2, (_full_cache(k, max_len), _full_cache(v, max_len))

            x, (ks, vs) = jax.lax.scan(inner, x, glp)
            x, ck, cv = cross_block_full(clp, x, ctx, cfg)
            return x, (ks, vs, ck, cv)

        x, (ks, vs, cks, cvs) = jax.lax.scan(
            group_body, x, (grouped, params["cross_layers"]))
        lk = ks.reshape(cfg.n_layers, *ks.shape[2:])
        lv = vs.reshape(cfg.n_layers, *vs.shape[2:])
        cache = {"k": lk, "v": lv, "cross_k": cks, "cross_v": cvs,
                 "pos": jnp.full((), s, jnp.int32)}
        return lm_head(params, x[:, -1:, :], cfg)[:, 0], cache

    n_glob = n_global_layers(cfg) if dual else 0
    gk0 = jnp.zeros((max(n_glob, 1), b, max_len, cfg.n_kv_heads, cfg.dh),
                    jnp.bfloat16)

    def body(carry, xs):
        x, gk, gv = carry
        lp, flag, gslot = xs
        if dual:
            def global_fn(ops_in):
                x_, gk_, gv_ = ops_in
                xo, k, v, _ = block_full(lp, x_, cfg, positions=positions,
                                         window=None, train=False)
                gk_ = jax.lax.dynamic_update_slice(
                    gk_, _full_cache(k, max_len)[None].astype(gk_.dtype),
                    (gslot, 0, 0, 0, 0))
                gv_ = jax.lax.dynamic_update_slice(
                    gv_, _full_cache(v, max_len)[None].astype(gv_.dtype),
                    (gslot, 0, 0, 0, 0))
                return xo, k, v, gk_, gv_

            def local_fn(ops_in):
                x_, gk_, gv_ = ops_in
                xo, k, v, _ = block_full(lp, x_, cfg, positions=positions,
                                         window=w, train=False)
                return xo, k, v, gk_, gv_

            x, k, v, gk, gv = jax.lax.cond(flag, global_fn, local_fn,
                                           (x, gk, gv))
            lc = local_cache_len(cfg, max_len)
            ys = (_windowed_cache(k, lc, max_len),
                  _windowed_cache(v, lc, max_len))
        else:
            x, k, v, _ = block_full(lp, x, cfg, positions=positions,
                                    window=w, train=False)
            if w:
                ys = (_windowed_cache(k, w, max_len),
                      _windowed_cache(v, w, max_len))
            elif quant:
                k8, ksn = attn.kv_quantize(k)
                v8, vsn = attn.kv_quantize(v)
                ys = (_full_cache(k8, max_len), _full_cache(v8, max_len),
                      _full_cache(ksn, max_len), _full_cache(vsn, max_len))
            else:
                ys = (_full_cache(k, max_len), _full_cache(v, max_len))
        return (x, gk, gv), ys

    quant = attn.kv_int8_enabled(cfg)
    (x, gk, gv), ys = jax.lax.scan(
        body, (x, gk0, gk0), (params["layers"], flags, gslots))
    if quant:
        ks, vs, kss, vss = ys
        cache = {"k": ks, "v": vs, "k_scale": kss, "v_scale": vss,
                 "pos": jnp.full((), s, jnp.int32)}
    else:
        ks, vs = ys
        cache = {"k": ks, "v": vs, "pos": jnp.full((), s, jnp.int32)}
    if dual:
        cache["global_k"], cache["global_v"] = gk, gv
    if length is None:
        last = x[:, -1:, :]
    else:
        n = jnp.asarray(length, jnp.int32)
        last = jax.lax.dynamic_slice_in_dim(x, n - 1, 1, axis=1)
        cache["pos"] = jnp.asarray(n, jnp.int32)
    logits = lm_head(params, last, cfg)[:, 0]
    return logits, cache


# --------------------------------------------------------------------------
# Decode — one token against the cache
# --------------------------------------------------------------------------


def decode_step(params: dict, token: jax.Array, cache: dict,
                cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """token: (B,) int32. Returns (logits (B,V), updated cache)."""
    b = token.shape[0]
    pos = cache["pos"]  # scalar absolute position of the new token
    x = embed_tokens(params, token[:, None], cfg)
    flags, gslots = _layer_flags(cfg)
    dual = _dual(cfg)
    w = cfg.sliding_window
    rolled = w is not None and cache["k"].shape[2] <= w

    if cfg.family == "vlm":
        every = cfg.cross_attn_every
        g = cfg.n_layers // every
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape(g, every, *a.shape[1:]), params["layers"])
        kg = cache["k"].reshape(g, every, *cache["k"].shape[1:])
        vg = cache["v"].reshape(g, every, *cache["v"].shape[1:])

        def group_body(x, xs):
            glp, clp, kge, vge, ck, cv = xs

            def inner(x2, lxs):
                lp, kc, vc = lxs
                x2, kc, vc = block_decode(lp, x2, kc, vc, pos, cfg,
                                          rolled=False, window=None)
                return x2, (kc, vc)

            x, (kc, vc) = jax.lax.scan(inner, x, (glp, kge, vge))
            x = cross_block_decode(clp, x, ck, cv, cfg)
            return x, (kc, vc)

        x, (kn, vn) = jax.lax.scan(
            group_body, x,
            (grouped, params["cross_layers"], kg, vg,
             cache["cross_k"], cache["cross_v"]))
        new_cache = dict(cache)
        new_cache["k"] = kn.reshape(cfg.n_layers, *kn.shape[2:])
        new_cache["v"] = vn.reshape(cfg.n_layers, *vn.shape[2:])
        new_cache["pos"] = pos + 1
        return lm_head(params, x, cfg)[:, 0], new_cache

    gk = cache.get("global_k", jnp.zeros((1,) + cache["k"].shape[1:],
                                         cache["k"].dtype))
    gv = cache.get("global_v", gk)

    if attn.kv_int8_enabled(cfg):
        def qbody(x, xs):
            lp, kc, vc, ksc, vsc = xs
            x, kc, vc, ksc, vsc = block_decode_quant(lp, x, kc, vc, ksc,
                                                     vsc, pos, cfg)
            return x, (kc, vc, ksc, vsc)

        x, (kn, vn, ksn, vsn) = jax.lax.scan(
            qbody, x, (params["layers"], cache["k"], cache["v"],
                       cache["k_scale"], cache["v_scale"]))
        new_cache = dict(cache, k=kn, v=vn, k_scale=ksn, v_scale=vsn,
                         pos=pos + 1)
        return lm_head(params, x, cfg)[:, 0], new_cache

    def body(carry, xs):
        x, gk, gv = carry
        lp, flag, gslot, kc, vc = xs
        if dual:
            def global_fn(ops_in):
                x_, gk_, gv_, kc_, vc_ = ops_in
                gkl = jax.lax.dynamic_index_in_dim(gk_, gslot, 0,
                                                   keepdims=False)
                gvl = jax.lax.dynamic_index_in_dim(gv_, gslot, 0,
                                                   keepdims=False)
                xo, gkl, gvl = block_decode(lp, x_, gkl, gvl, pos, cfg,
                                            rolled=False, window=None)
                gk_ = jax.lax.dynamic_update_slice(
                    gk_, gkl[None], (gslot, 0, 0, 0, 0))
                gv_ = jax.lax.dynamic_update_slice(
                    gv_, gvl[None], (gslot, 0, 0, 0, 0))
                return xo, gk_, gv_, kc_, vc_

            def local_fn(ops_in):
                x_, gk_, gv_, kc_, vc_ = ops_in
                xo, kc_, vc_ = block_decode(lp, x_, kc_, vc_, pos, cfg,
                                            rolled=True, window=w)
                return xo, gk_, gv_, kc_, vc_

            x, gk, gv, kc, vc = jax.lax.cond(flag, global_fn, local_fn,
                                             (x, gk, gv, kc, vc))
        else:
            x, kc, vc = block_decode(lp, x, kc, vc, pos, cfg,
                                     rolled=rolled, window=w)
        return (x, gk, gv), (kc, vc)

    (x, gk, gv), (kn, vn) = jax.lax.scan(
        body, (x, gk, gv), (params["layers"], flags, gslots,
                            cache["k"], cache["v"]))
    new_cache = dict(cache, k=kn, v=vn, pos=pos + 1)
    if dual:
        new_cache["global_k"], new_cache["global_v"] = gk, gv
    return lm_head(params, x, cfg)[:, 0], new_cache


# --------------------------------------------------------------------------
# Paged decode — one token against block-paged KV pools
# --------------------------------------------------------------------------


def supports_paged(cfg: ModelConfig) -> bool:
    """Paged decode covers the full-cache dense/MoE paths: every KV row is
    addressed by absolute position, so block tables substitute directly.
    Rolled sliding-window and dual local:global caches fold positions
    (slot = pos % W) and would alias rows across blocks."""
    return (cfg.family in ("dense", "moe")
            and cfg.sliding_window is None
            and cfg.local_global_ratio == 0)


def _paged_layer_scan(params: dict, x: jax.Array, cache: dict,
                      keys: tuple[str, ...], layer_fn
                      ) -> tuple[jax.Array, dict]:
    """Run ``layer_fn(lp, x, pools, layer) -> (x, pools)`` over the layers
    with the stacked (L, N, bs, ...) pools ``cache[keys]`` in the carry.

    Each layer scatters its rows into the carried pools and gathers its
    pages from them by layer index, so a donated cache is updated in
    place.  Scanning the pools as ``xs``/``ys`` instead slices every
    layer's pool out and re-stacks it, which XLA compiles to whole-pool
    copies on every step.  Returns (x, cache with the updated pools).
    """
    def body(carry, xs):
        x, pools = carry
        lp, layer = xs
        return layer_fn(lp, x, pools, layer), None

    n_layers = cache[keys[0]].shape[0]
    (x, pools), _ = jax.lax.scan(
        body, (x, {k: cache[k] for k in keys}),
        (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    return x, dict(cache, **pools)


def decode_step_paged(params: dict, token: jax.Array, cache: dict,
                      block_tables: jax.Array, pos: jax.Array,
                      cfg: ModelConfig,
                      active: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, dict]:
    """One decode step against block-paged KV pools.

    token: (B,) int32; cache: {"k","v"} of (L, N, bs, K, Dh) physical
    blocks shared across the batch (+ int8 scale pools when KV-int8 is
    on); block_tables: (B, M) int32 mapping each sequence's logical block
    slots to physical blocks; pos: (B,) int32 absolute positions;
    ``active`` ((B,), optional) suppresses free slots' KV writes.  The
    caller owns block allocation and position bookkeeping — this step
    only writes one row per sequence and attends its table, in place in
    the pools the layer scan carries (``_paged_layer_scan``).  Returns
    (logits (B, V), updated cache).
    """
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged decode requires a full-cache dense/moe config, "
            f"got {cfg.name} ({cfg.family})")
    x = embed_tokens(params, token[:, None], cfg)
    pos = jnp.asarray(pos, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)

    if attn.kv_int8_enabled(cfg):
        keys = ("k", "v", "k_scale", "v_scale")

        def layer_fn(lp, x, p, layer):
            x, *pools = block_decode_paged_quant(
                lp, x, *(p[k] for k in keys), block_tables, pos, cfg,
                active, layer)
            return x, dict(zip(keys, pools))
    else:
        keys = ("k", "v")

        def layer_fn(lp, x, p, layer):
            x, kc, vc = block_decode_paged(lp, x, p["k"], p["v"],
                                           block_tables, pos, cfg, active,
                                           layer)
            return x, {"k": kc, "v": vc}

    x, new_cache = _paged_layer_scan(params, x, cache, keys, layer_fn)
    return lm_head(params, x, cfg)[:, 0], new_cache


# --------------------------------------------------------------------------
# Fused decode — sample on device, never ship logits to the host
# --------------------------------------------------------------------------


def greedy_tokens(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Greedy next tokens, clipped to the real vocab (padded-vocab argmax
    can land on a pad logit only through float ties; the clip keeps the
    device sampler bit-identical to the engine's old host-side path)."""
    from repro.kernels import ops
    return ops.greedy_sample(logits, cfg.vocab_size)


def sampled_tokens(logits: jax.Array, cfg: ModelConfig, key, sampling
                   ) -> jax.Array:
    """Shared fused sampler: greedy when no key/sampling config is given,
    otherwise ``ops.sample_tokens`` (temperature / top-k / top-p) with the
    provided key.  Every family's fused token step — transformer, rwkv6,
    hybrid, encdec — funnels through here so the one-sync guarantee and
    the key-stream discipline are identical across families."""
    from repro.kernels import ops
    if key is None or sampling is None:
        return greedy_tokens(logits, cfg)
    return ops.sample_tokens(logits, key, cfg.vocab_size,
                             temperature=sampling.temperature,
                             top_k=sampling.top_k, top_p=sampling.top_p)


def decode_step_tokens(params: dict, token: jax.Array, cache: dict,
                       cfg: ModelConfig, key=None, sampling=None):
    """``decode_step`` with the sampler fused in: returns
    ``((B,) int32 next tokens, updated cache)`` — the serving engine's
    sync-free hot path pulls B int32s per round instead of (B, V) logits.
    With a PRNG ``key`` (threaded and donated exactly like the token
    vector) the step splits it in-jit, samples stochastically, and
    additionally returns the advanced key.
    """
    logits, cache = decode_step(params, token, cache, cfg)
    if key is None:
        return greedy_tokens(logits, cfg), cache
    key, sub = jax.random.split(key)
    return sampled_tokens(logits, cfg, sub, sampling), cache, key


def decode_step_paged_tokens(params: dict, token: jax.Array, cache: dict,
                             block_tables: jax.Array, pos: jax.Array,
                             active: jax.Array, cfg: ModelConfig,
                             key=None, sampling=None):
    """Fused paged round: sample on device AND advance the per-slot
    position vector in-jit (``pos + active``), so the engine keeps
    ``pos`` device-resident and only uploads it when admission, release,
    or migration touched the host mirror.  Free slots (``active == 0``)
    neither write KV nor advance.  Returns (tokens, cache, new pos), plus
    the advanced PRNG key when one is threaded through.
    """
    active = jnp.asarray(active, jnp.int32)
    logits, cache = decode_step_paged(params, token, cache, block_tables,
                                      pos, cfg, active=active)
    if key is None:
        return greedy_tokens(logits, cfg), cache, pos + active
    key, sub = jax.random.split(key)
    return (sampled_tokens(logits, cfg, sub, sampling), cache,
            pos + active, key)


# --------------------------------------------------------------------------
# Speculative verify — score a k+1 window in one forward
# --------------------------------------------------------------------------


def block_verify(lp: dict, x: jax.Array, kc: jax.Array, vc: jax.Array,
                 pos: jax.Array, cfg: ModelConfig
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kc, vc = attn.attn_verify(lp["attn"], h, kc, vc, pos, cfg)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, _ = _ffn(lp, h, cfg, train=False)
    return x + m, kc, vc


def block_verify_paged(lp: dict, x: jax.Array, kc: jax.Array, vc: jax.Array,
                       block_tables: jax.Array, pos: jax.Array,
                       cfg: ModelConfig,
                       active: Optional[jax.Array] = None,
                       layer: Optional[jax.Array] = None
                       ) -> tuple[jax.Array, jax.Array, jax.Array]:
    h = rms_norm(x, lp["ln1"], cfg.norm_eps)
    a, kc, vc = attn.attn_verify_paged(lp["attn"], h, kc, vc,
                                       block_tables, pos, cfg, active, layer)
    x = x + a
    h = rms_norm(x, lp["ln2"], cfg.norm_eps)
    m, _ = _ffn(lp, h, cfg, train=False)
    return x + m, kc, vc


def supports_speculative(cfg: ModelConfig) -> bool:
    """The verify step addresses KV rows by absolute position (like the
    paged plane) and writes a W-row window per round, so it covers the
    same full-cache dense/MoE configs — minus the int8 KV variant, whose
    per-row scale pools would need a windowed quantized writer."""
    return supports_paged(cfg) and not attn.kv_int8_enabled(cfg)


def verify_step(params: dict, tokens: jax.Array, cache: dict,
                cfg: ModelConfig) -> tuple[jax.Array, dict]:
    """Score a speculative window in one forward against a dense cache.

    tokens: (B, W) int32 — [last emitted token, k draft tokens], W=k+1.
    Writes the window's KV rows at cache["pos"]..pos+W-1 and returns
    (logits (B, W, V), updated cache); ``logits[:, j]`` is the target
    distribution for the token *after* window position j.  ``cache["pos"]``
    is left untouched — the caller folds the accepted-prefix length in
    (the rejected rows beyond the new position are garbage the causal
    mask hides until they are overwritten, exactly like bucketed
    prefill's padded tail).
    """
    if not supports_speculative(cfg):
        raise NotImplementedError(
            f"speculative verify requires a full-cache dense/moe config, "
            f"got {cfg.name} ({cfg.family})")
    b = tokens.shape[0]
    pos = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(cache["pos"])),
                           (b,)).astype(jnp.int32)
    x = embed_tokens(params, tokens, cfg)

    def body(x, xs):
        lp, kc, vc = xs
        x, kc, vc = block_verify(lp, x, kc, vc, pos, cfg)
        return x, (kc, vc)

    x, (kn, vn) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"]))
    return lm_head(params, x, cfg), dict(cache, k=kn, v=vn)


def verify_step_paged(params: dict, tokens: jax.Array, cache: dict,
                      block_tables: jax.Array, pos: jax.Array,
                      cfg: ModelConfig,
                      active: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, dict]:
    """``verify_step`` against block-paged KV pools: writes the window's
    rows through the per-position paged scatter (inactive slots drop) and
    returns (logits (B, W, V), updated cache).  Position bookkeeping
    stays with the caller."""
    if not supports_speculative(cfg):
        raise NotImplementedError(
            f"speculative verify requires a full-cache dense/moe config, "
            f"got {cfg.name} ({cfg.family})")
    x = embed_tokens(params, tokens, cfg)
    pos = jnp.asarray(pos, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)

    def layer_fn(lp, x, p, layer):
        x, kc, vc = block_verify_paged(lp, x, p["k"], p["v"], block_tables,
                                       pos, cfg, active, layer)
        return x, {"k": kc, "v": vc}

    x, cache = _paged_layer_scan(params, x, cache, ("k", "v"), layer_fn)
    return lm_head(params, x, cfg), cache
