"""GQA attention blocks: projections, full-sequence and decode paths, caches.

Cache conventions (see kvcache.py):
  * full cache:   (B, S_max, K, Dh), write slot = position.
  * rolled cache: (B, W, K, Dh) for sliding-window layers, slot = pos % W;
    slot contents are reconstructible from the current position, so no
    per-slot position array is needed.

Rotary embeddings are applied before caching (post-rope keys in cache).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.sharding import current_mesh, named, serve_tp
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.layers import PSpec, apply_rope

NEG_INF = -1e30


def _baseline_mode() -> bool:
    """REPRO_BASELINE=1 disables the beyond-paper perf fixes so §Perf can
    measure baseline vs. optimized with identical analysis code."""
    import os
    return os.environ.get("REPRO_BASELINE", "") == "1"


def _tp_size() -> int:
    mesh = current_mesh()
    return int(mesh.shape.get("model", 1)) if mesh is not None else 1


def _shard_heads(q: jax.Array, k: jax.Array, v: jax.Array
                 ) -> tuple[jax.Array, jax.Array, jax.Array, int]:
    """Make full-sequence attention shard over the TP axis for ANY head
    count (§Perf iteration A1, beyond-paper).

    Head counts that don't divide the model axis (qwen2's 28q/4kv, hymba's
    25q/5kv) leave XLA no head sharding, so it *replicates the whole
    attention computation 16x*.  Fix: pad Q heads to the next multiple of
    TP and expand K/V to one kv head per (padded) Q head — the flash einsum
    then has a head axis every mesh size divides.  The K/V expansion is
    free at the FLOP level and its extra bytes are sharded away by the very
    axis it unlocks; padded-head outputs are sliced off.

    Returns (q', k', v', n_heads_orig).
    """
    tp = _tp_size()
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    if tp == 1 or (h % tp == 0 and n_kv % tp == 0) or _baseline_mode():
        return q, k, v, h
    h_pad = -(-h // tp) * tp
    g = h // n_kv
    # kv head serving q head i is i // g; padded heads reuse head 0.
    kv_idx = jnp.concatenate([jnp.arange(h) // g,
                              jnp.zeros((h_pad - h,), jnp.int32)])
    if h_pad != h:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, h_pad - h), (0, 0)))
    k = jnp.take(k, kv_idx, axis=2)
    v = jnp.take(v, kv_idx, axis=2)
    q = named(q, "batch", "seq", "heads", None)
    k = named(k, "batch", "seq", "heads", None)
    v = named(v, "batch", "seq", "heads", None)
    return q, k, v, h


def attn_specs(cfg: ModelConfig, cross: bool = False) -> dict[str, PSpec]:
    d, hq, kv = cfg.d_model, cfg.q_dim, cfg.kv_dim
    s = {
        "wq": PSpec((d, hq), ("fsdp", "tp")),
        "wk": PSpec((d, kv), ("fsdp", "tp")),
        "wv": PSpec((d, kv), ("fsdp", "tp")),
        "wo": PSpec((hq, d), ("tp", "fsdp")),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = PSpec((hq,), ("tp",), init="zeros")
        s["bk"] = PSpec((kv,), ("tp",), init="zeros")
        s["bv"] = PSpec((kv,), ("tp",), init="zeros")
    return s


def _project_q(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    q = x @ params["wq"]
    if "bq" in params:
        q = q + params["bq"]
    b, s, _ = x.shape
    q = q.reshape(b, s, cfg.n_heads, cfg.dh)
    return named(q, "batch", "seq", "heads", None)


def _project_kv(params: dict, x: jax.Array, cfg: ModelConfig
                ) -> tuple[jax.Array, jax.Array]:
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bk" in params:
        k = k + params["bk"]
        v = v + params["bv"]
    b, s, _ = x.shape
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.dh)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.dh)
    return (named(k, "batch", "seq", "kv_heads", None),
            named(v, "batch", "seq", "kv_heads", None))


def _output(params: dict, o: jax.Array) -> jax.Array:
    b, s, h, dh = o.shape
    o = named(o, "batch", "seq", "heads", None)
    o = o.reshape(b, s, h * dh)
    if serve_tp() > 1:
        # Serving TP is column-only/exact: gather the head shards BEFORE
        # the output projection so wo's contraction runs in full on every
        # device — an all-gather is bitwise-exact, a split-K all-reduce
        # is not (bf16 reassociation flips near-tie argmax tokens).
        o = named(o, "batch", "seq", None)
    out = o @ params["wo"]
    return named(out, "batch", "seq", None)


# --------------------------------------------------------------------------
# Full-sequence (training / prefill)
# --------------------------------------------------------------------------


def attn_full(params: dict, x: jax.Array, cfg: ModelConfig, *,
              positions: jax.Array, window: Optional[int] = None,
              causal: bool = True, block_q: int = 512, block_k: int = 512
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Self-attention over the whole sequence.

    Returns (output, k, v) — k/v post-rope, for the caller to cache.
    """
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    qs, ks, vs, h = _shard_heads(q, k, v)
    o = ops.flash_attention(qs, ks, vs, causal=causal, window=window,
                            block_q=block_q, block_k=block_k)
    return _output(params, o[:, :, :h]), k, v


def cross_attn_full(params: dict, x: jax.Array, context_kv: tuple,
                    cfg: ModelConfig) -> jax.Array:
    """Cross-attention to precomputed context k/v (no mask, no rope)."""
    q = _project_q(params, x, cfg)
    k, v = context_kv
    qs, ks, vs, h = _shard_heads(q, k, v)
    o = ops.flash_attention(qs, ks, vs, causal=False)
    return _output(params, o[:, :, :h])


def context_kv(params: dict, ctx: jax.Array, cfg: ModelConfig
               ) -> tuple[jax.Array, jax.Array]:
    """Project encoder/image context into this layer's k/v (cacheable)."""
    return _project_kv(params, ctx, cfg)


# --------------------------------------------------------------------------
# int8 KV-cache quantization (§Perf D — decode cells are KV-bandwidth bound)
# --------------------------------------------------------------------------


def kv_int8_enabled(cfg: ModelConfig) -> bool:
    """REPRO_KV_INT8=1 stores full (non-rolled) dense/MoE KV caches as int8
    with per-(position, kv-head) scales — halves decode HBM traffic."""
    import os
    return (os.environ.get("REPRO_KV_INT8", "") == "1"
            and cfg.family in ("dense", "moe")
            and cfg.sliding_window is None
            and cfg.local_global_ratio == 0)


def kv_quantize(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(B,S,K,D) bf16 -> (int8 codes, (B,S,K,1) bf16 scales)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(xf).max(axis=-1, keepdims=True) / 127.0,
                        1e-8)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def kv_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)).astype(jnp.bfloat16)


# --------------------------------------------------------------------------
# Decode (one token against a cache)
# --------------------------------------------------------------------------


def cache_write(cache: jax.Array, new: jax.Array, slot: jax.Array) -> jax.Array:
    """Write (B, 1, K, Dh) into (B, C, K, Dh) at ``slot`` (scalar or (B,))."""
    if slot.ndim == 0:
        return jax.lax.dynamic_update_slice(
            cache, new.astype(cache.dtype), (0, slot, 0, 0))
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s, 0, 0))
    )(cache, new.astype(cache.dtype), slot)


def slot_gather(leaf: jax.Array, slot: jax.Array, batch_axis: int
                ) -> jax.Array:
    """Extract one decode slot as a batch-1 leaf (inverse of a slot merge).

    Used by the continuous-batching engine to inspect / migrate a single
    request's cache entry out of the persistent slot pool.
    """
    return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=batch_axis)


def _rolled_decode(q, kc, vc, pos, window):
    """Attention against a rolled cache: slot s holds position
    pos - ((pos - s) mod C); invalid when that position is negative."""
    b, _, h, d = q.shape
    c = kc.shape[1]
    n_kv = kc.shape[2]
    qf = q.astype(jnp.float32).reshape(b, 1, n_kv, h // n_kv, d) * d ** -0.5
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, kc.astype(jnp.float32))
    slots = jnp.arange(c)
    pos_b = jnp.broadcast_to(jnp.atleast_1d(pos), (b,))
    slot_pos = pos_b[:, None] - jnp.mod(pos_b[:, None] - slots[None, :], c)
    valid = slot_pos >= 0
    if window is not None and window < c:
        valid &= slot_pos > pos_b[:, None] - window
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vc.astype(jnp.float32))
    return o.reshape(b, 1, h, d).astype(q.dtype)


def attn_decode(params: dict, x: jax.Array, kc: jax.Array, vc: jax.Array,
                pos: jax.Array, cfg: ModelConfig, *,
                rolled: bool = False, window: Optional[int] = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token self-attention against (and updating) the cache.

    x: (B, 1, D); pos: scalar or (B,) absolute position of the new token.
    Returns (output, kc', vc').
    """
    b = x.shape[0]
    pos_arr = jnp.asarray(pos)
    positions = jnp.broadcast_to(jnp.atleast_1d(pos_arr), (b,))[:, None]
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    c = kc.shape[1]
    slot = jnp.mod(pos_arr, c) if rolled else pos_arr
    kc = cache_write(kc, k, slot)
    vc = cache_write(vc, v, slot)
    if rolled:
        o = _rolled_decode(q, kc, vc, pos_arr, window)
    else:
        cache_len = jnp.broadcast_to(jnp.atleast_1d(pos_arr), (b,)) + 1
        o = ops.decode_attention(q, kc, vc, cache_len.astype(jnp.int32),
                                 window=window)
    return _output(params, o), kc, vc


def paged_cache_write(pages: jax.Array, new: jax.Array,
                      block_tables: jax.Array, pos: jax.Array,
                      active: Optional[jax.Array] = None,
                      layer: Optional[jax.Array] = None) -> jax.Array:
    """Write one token's (B, 1, K, Dh) K/V into (N, bs, K, Dh) pages, or,
    given ``layer``, into that layer of a stacked (L, N, bs, K, Dh) pool
    (one scatter at ``[layer, blk, pos % bs]``, so a pool carried through
    the layer scan is updated in place).

    Each sequence's row lands in physical block ``tables[b, pos[b]//bs]``
    at offset ``pos[b] % bs``.  Live sequences own disjoint WRITABLE
    blocks, so the scatter never collides; free decode slots all target
    the shared null block, whose contents are never attended.  Under
    prefix sharing the write contract is stricter: the block a sequence
    writes must be exclusively owned (refcount 1) — the engine resolves
    copy-on-write and asserts that before every dispatched round, so a
    shared (refcount > 1) block is never named by a write-position row
    of ``block_tables``.

    ``active`` ((B,) int32/bool, optional) drops inactive sequences' rows
    entirely (scatter ``mode="drop"`` on an out-of-range block index)
    instead of scattering them into the null block — free decode slots in
    the fused hot path then write nothing at all, so the null page stays
    zero and the scatter never has colliding free-slot rows.  The drop
    sentinel must be ``>= n_blocks``: a negative index would be
    NORMALIZED (to the last physical block — a live sequence's page)
    before out-of-bounds handling ever sees it.
    """
    lead = () if layer is None else (layer,)
    n_blocks, bs = pages.shape[len(lead):len(lead) + 2]
    blk = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    if active is not None:
        blk = jnp.where(active.astype(bool), blk, n_blocks)
    row = new[:, 0].astype(pages.dtype)  # (B, K, Dh)
    if pages.shape[-1] == 1:
        # int8 scale pools: index each head too.  A (K, 1) update window
        # makes the TPU lay the whole stacked pool out K-minor, padded to
        # 128 lanes, and copy it in and out of the layer scan.
        heads = jnp.arange(row.shape[1])
        return pages.at[(*lead, blk[:, None], (pos % bs)[:, None], heads)
                        ].set(row, mode="drop")
    return pages.at[(*lead, blk, pos % bs)].set(row, mode="drop")


def attn_decode_paged(params: dict, x: jax.Array,
                      k_pages: jax.Array, v_pages: jax.Array,
                      block_tables: jax.Array, pos: jax.Array,
                      cfg: ModelConfig,
                      active: Optional[jax.Array] = None,
                      layer: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token self-attention against (and updating) a paged cache.

    x: (B, 1, D); k_pages/v_pages: (N, bs, K, Dh) physical blocks shared
    by the whole batch, or the stacked (L, N, bs, K, Dh) pools with the
    ``layer`` index to write and read; block_tables: (B, M) int32; pos:
    (B,) absolute position of each sequence's new token; ``active``
    optionally masks free slots' writes out (see paged_cache_write).
    Returns (output, k', v').
    """
    positions = pos[:, None]
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k_pages = paged_cache_write(k_pages, k, block_tables, pos, active, layer)
    v_pages = paged_cache_write(v_pages, v, block_tables, pos, active, layer)
    cache_len = (pos + 1).astype(jnp.int32)
    o = ops.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                   cache_len, layer=layer)
    return _output(params, o), k_pages, v_pages


def cache_write_window(cache: jax.Array, new: jax.Array, start: jax.Array
                       ) -> jax.Array:
    """Write (B, W, K, Dh) into (B, C, K, Dh) at per-sequence row ``start``
    (a (B,) vector) — the W-row generalization of ``cache_write`` used by
    the speculative verify step.  Requires ``start + W <= C`` (the engine
    reserves the +k speculation margin at submit time); XLA's clamped
    start would otherwise silently shift the window."""
    return jax.vmap(
        lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s, 0, 0))
    )(cache, new.astype(cache.dtype), start)


def attn_verify(params: dict, x: jax.Array, kc: jax.Array, vc: jax.Array,
                pos: jax.Array, cfg: ModelConfig
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """W-token verify attention against (and updating) a dense cache.

    x: (B, W, D) — the speculative window [last accepted token, k draft
    tokens]; pos: (B,) absolute position of the window start.  Writes the
    window's K/V rows at pos..pos+W-1 and attends them with the
    per-query-row causal mask (window query j sees rows < pos + j + 1).
    Returns (output (B, W, D), kc', vc').
    """
    b, w, _ = x.shape
    pos_b = jnp.broadcast_to(jnp.atleast_1d(jnp.asarray(pos)), (b,))
    positions = pos_b[:, None] + jnp.arange(w)[None, :]
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    kc = cache_write_window(kc, k, pos_b)
    vc = cache_write_window(vc, v, pos_b)
    o = ops.verify_attention(q, kc, vc, pos_b.astype(jnp.int32))
    return _output(params, o), kc, vc


def attn_verify_paged(params: dict, x: jax.Array,
                      k_pages: jax.Array, v_pages: jax.Array,
                      block_tables: jax.Array, pos: jax.Array,
                      cfg: ModelConfig,
                      active: Optional[jax.Array] = None,
                      layer: Optional[jax.Array] = None
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """W-token verify attention against (and updating) a paged cache.

    Scatters the window's rows one position at a time (W is small — the
    draft length plus one) through ``paged_cache_write`` so inactive
    slots' rows drop and the COW write contract stays per-position, then
    attends with the per-query-row causal mask.  Pages and ``layer`` as
    in ``attn_decode_paged``.
    """
    w = x.shape[1]
    positions = pos[:, None] + jnp.arange(w)[None, :]
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    for j in range(w):
        k_pages = paged_cache_write(k_pages, k[:, j:j + 1], block_tables,
                                    pos + j, active, layer)
        v_pages = paged_cache_write(v_pages, v[:, j:j + 1], block_tables,
                                    pos + j, active, layer)
    o = ops.paged_verify_attention(q, k_pages, v_pages, block_tables,
                                   pos.astype(jnp.int32), layer=layer)
    return _output(params, o), k_pages, v_pages


def attn_decode_paged_quant(params: dict, x: jax.Array,
                            k_pages: jax.Array, v_pages: jax.Array,
                            ks_pages: jax.Array, vs_pages: jax.Array,
                            block_tables: jax.Array, pos: jax.Array,
                            cfg: ModelConfig,
                            active: Optional[jax.Array] = None,
                            layer: Optional[jax.Array] = None
                            ) -> tuple[jax.Array, jax.Array, jax.Array,
                                       jax.Array, jax.Array]:
    """attn_decode_paged against int8 code + scale pages (§Perf D)."""
    positions = pos[:, None]
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k8, ks_new = kv_quantize(k)
    v8, vs_new = kv_quantize(v)
    k_pages = paged_cache_write(k_pages, k8, block_tables, pos, active,
                                layer)
    v_pages = paged_cache_write(v_pages, v8, block_tables, pos, active,
                                layer)
    ks_pages = paged_cache_write(ks_pages, ks_new, block_tables, pos, active,
                                 layer)
    vs_pages = paged_cache_write(vs_pages, vs_new, block_tables, pos, active,
                                 layer)
    cache_len = (pos + 1).astype(jnp.int32)
    o = ops.paged_decode_attention_quant(q, k_pages, v_pages, ks_pages,
                                         vs_pages, block_tables, cache_len,
                                         layer=layer)
    return _output(params, o), k_pages, v_pages, ks_pages, vs_pages


def attn_decode_quant(params: dict, x: jax.Array,
                      kc: jax.Array, vc: jax.Array,
                      ksc: jax.Array, vsc: jax.Array,
                      pos: jax.Array, cfg: ModelConfig
                      ) -> tuple[jax.Array, jax.Array, jax.Array,
                                 jax.Array, jax.Array]:
    """attn_decode against int8 caches (kc/vc int8, ksc/vsc (B,C,K,1)
    scales).  The dequantize fuses into the attention consumer, so HBM
    reads stay int8-sized; on the TPU target the Pallas decode kernel
    takes the int8 refs directly."""
    b = x.shape[0]
    pos_arr = jnp.asarray(pos)
    positions = jnp.broadcast_to(jnp.atleast_1d(pos_arr), (b,))[:, None]
    q = _project_q(params, x, cfg)
    k, v = _project_kv(params, x, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    k8, ks_new = kv_quantize(k)
    v8, vs_new = kv_quantize(v)
    kc = cache_write(kc, k8, pos_arr)
    vc = cache_write(vc, v8, pos_arr)
    ksc = cache_write(ksc, ks_new, pos_arr)
    vsc = cache_write(vsc, vs_new, pos_arr)
    cache_len = (jnp.broadcast_to(jnp.atleast_1d(pos_arr), (b,)) + 1
                 ).astype(jnp.int32)
    o = ops.decode_attention_quant(q, kc, vc, ksc, vsc, cache_len)
    return _output(params, o), kc, vc, ksc, vsc


def cross_attn_decode(params: dict, x: jax.Array,
                      ck: jax.Array, cv: jax.Array,
                      cfg: ModelConfig) -> jax.Array:
    """One-token cross-attention against a precomputed context cache."""
    q = _project_q(params, x, cfg)
    b = x.shape[0]
    s_ctx = ck.shape[1]
    cache_len = jnp.full((b,), s_ctx, jnp.int32)
    o = ops.decode_attention(q, ck, cv, cache_len)
    return _output(params, o)
