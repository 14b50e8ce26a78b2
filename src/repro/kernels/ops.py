"""Attention / scan ops: jit'd wrappers that dispatch to an implementation.

Backends:
  * ``pallas`` — the TPU kernels in this package (``pl.pallas_call``),
    compiled on a TPU and interpreted elsewhere (tests only — slow; see
    ``repro.kernels.interpret_mode``).  The flash, wkv6 and ssm kernels
    are refused by the v5e compiler, so those ops raise on a TPU.
  * ``xla``    — pure-jnp *chunked* implementations with online softmax.
    Memory-bounded like the kernels (never materializes S x S), compiles to
    compact While-loop HLO, and is the default path inside the models.
  * ``ref``    — naive full-matrix oracles from ``ref.py`` (tests only).

The models call these wrappers; the dry-run therefore lowers the xla path,
and kernel tests assert pallas == xla == ref over shape/dtype sweeps.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref

DEFAULT_BACKEND = "xla"

# Pallas kernels the v5e compiler refuses.  Each streams one head per
# block, (1, block, 1, d) over a (B, S, H, d) array, and Mosaic requires a
# block's last two dims to be (8, 128)-aligned or the array's own.  The
# decode kernels keep the whole KV-head axis per block for this reason.
def _refuse_on_tpu(op: str) -> None:
    if jax.default_backend() == "tpu":
        raise NotImplementedError(
            f"{op}: the TPU compiler refuses its Pallas kernel (a one-head "
            f"(1, d) block over an (H, d) array); use backend='xla'")

NEG_INF = -1e30


def _gqa_expand(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, S, H, D) -> (B, S, K, G, D) grouped by kv head."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def _block_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
                window: Optional[int]) -> jax.Array:
    """(bq, bk) validity mask from absolute positions."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        m &= k_pos[None, :] > q_pos[:, None] - window
    return m


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "backend"))
def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, K, D)
    v: jax.Array,  # (B, Sk, K, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """Multi-head GQA attention, O(S) memory. Returns (B, Sq, H, D)."""
    if backend == "ref":
        return _ref.mha_reference(q, k, v, causal=causal, window=window,
                                  q_offset=q_offset)
    if backend == "pallas":
        _refuse_on_tpu("flash_attention")
        from repro.kernels import flash_attention as _fa
        return _fa.flash_attention_pallas(q, k, v, causal=causal,
                                          window=window, q_offset=q_offset,
                                          block_q=block_q, block_k=block_k)
    return _xla_flash(q, k, v, causal=causal, window=window,
                      q_offset=q_offset, block_q=block_q, block_k=block_k)


def _xla_flash(q, k, v, *, causal, window, q_offset, block_q, block_k):
    orig_dtype = q.dtype
    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    g = h // n_kv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    # Pad ragged tails up to block multiples (hymba's +meta-token seqs,
    # vision cross-attention ctx lengths); padded keys are masked via
    # ``sk_valid`` below and padded query rows sliced off at the end.
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    sq_valid, sk_valid = sq, sk
    if pad_q or pad_k:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        sq, sk = sq + pad_q, sk + pad_k
    scale = d ** -0.5
    qg = _gqa_expand(q, n_kv).astype(jnp.float32) * scale  # (B,Sq,K,G,D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    nq, nk = sq // block_q, sk // block_k

    q_blocks = qg.reshape(b, nq, block_q, n_kv, g, d).transpose(1, 0, 3, 4, 2, 5)
    k_blocks = kf.reshape(b, nk, block_k, n_kv, d).transpose(1, 0, 3, 2, 4)
    v_blocks = vf.reshape(b, nk, block_k, n_kv, d).transpose(1, 0, 3, 2, 4)

    # Windowed attention only ever reaches a bounded, *contiguous* range of
    # KV blocks per Q block — scan that constant-length range from a
    # dynamic start instead of all nk blocks (16x fewer block-pairs for
    # hymba's W=1024 at 32k ctx; static trip count, exact HLO accounting).
    import os
    n_win = None
    if window is not None and os.environ.get("REPRO_BASELINE", "") != "1":
        n_win = min(nk, (window - 1 + block_q) // block_k + 2)

    def attend(carry, ik, kb, vb, q_pos, qb):
        acc, m, l = carry
        k_pos = ik * block_k + jnp.arange(block_k)
        s = jnp.einsum("bkgqd,bksd->bkgqs", qb, kb)  # (B,K,G,bq,bk)
        mask = _block_mask(q_pos, k_pos, causal, window)
        mask &= (k_pos < sk_valid)[None, :]  # padded keys are invalid
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqs,bksd->bkgqd", p, vb)
        return acc_new, m_new, l_new

    def one_q_block(iq, qb):  # qb: (B, K, G, bq, D)
        q_pos = q_offset + iq * block_q + jnp.arange(block_q)
        acc0 = jnp.zeros((b, n_kv, g, block_q, d), jnp.float32)
        m0 = jnp.full((b, n_kv, g, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, block_q), jnp.float32)

        if n_win is not None and n_win < nk:
            q_start = q_offset + iq * block_q
            start = jnp.clip((q_start - window + 1) // block_k,
                             0, nk - n_win)

            def kv_step_win(carry, j):
                ik = start + j
                kb = jax.lax.dynamic_index_in_dim(k_blocks, ik, 0, False)
                vb = jax.lax.dynamic_index_in_dim(v_blocks, ik, 0, False)
                return attend(carry, ik, kb, vb, q_pos, qb), None

            (acc, m, l), _ = jax.lax.scan(
                kv_step_win, (acc0, m0, l0), jnp.arange(n_win))
        else:
            def kv_step(carry, inputs):
                ik, kb, vb = inputs  # kb/vb: (B, K, bk, D)
                return attend(carry, ik, kb, vb, q_pos, qb), None

            (acc, m, l), _ = jax.lax.scan(
                kv_step, (acc0, m0, l0),
                (jnp.arange(nk), k_blocks, v_blocks))
        return acc / jnp.maximum(l, 1e-30)[..., None]  # (B,K,G,bq,D)

    out, = jax.lax.map(
        lambda args: (one_q_block(*args),),
        (jnp.arange(nq), q_blocks))
    # out: (nq, B, K, G, bq, D) -> (B, Sq, H, D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, d)
    return out[:, :sq_valid].astype(orig_dtype)


@functools.partial(jax.jit, static_argnames=("vocab_size",))
def greedy_sample(logits: jax.Array, vocab_size: int) -> jax.Array:
    """Fused on-device greedy sampler: ``argmax`` over the (padded) vocab
    clipped to the real ``vocab_size``.  Returns int32 tokens with the
    leading batch shape of ``logits``.

    This is the device-side replacement for the serving engine's
    ``np.asarray(jnp.argmax(...))`` host round-trip: called inside the
    fused decode step it keeps the whole round on the accelerator (a
    (B,) int32 pull instead of a (B, V) logits pull), and XLA fuses the
    reduction into the lm-head consumer — no Pallas variant needed.
    """
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jnp.minimum(tok, vocab_size - 1)


def _filtered_logits(logits: jax.Array, vocab_size: int, temperature: float,
                     top_k: int, top_p: float) -> jax.Array:
    """Vocab-clipped, temperature-scaled logits with top-k / top-p (nucleus)
    filtering applied; excluded entries sit at ``NEG_INF``.

    The padded-vocab mask runs *before* the filters so a top-k/top-p cutoff
    can never be consumed by padding columns, and the top-1 entry always
    survives (top-p keeps the head of the nucleus even when
    ``top_p -> 0``).  Shared by ``sample_tokens`` and
    ``speculative_verify`` so the draft-proposal and verify distributions
    are computed by the same code path.
    """
    v = logits.shape[-1]
    idx = jnp.arange(v)
    logits = jnp.where(idx < vocab_size, logits.astype(jnp.float32), NEG_INF)
    logits = logits / max(temperature, 1e-6)
    if top_k > 0 and top_k < vocab_size:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, NEG_INF, logits)
    if top_p < 1.0:
        sort = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sort, axis=-1)
        # mass strictly before each sorted entry; keep while < top_p so the
        # nucleus always includes the argmax.
        before = jnp.cumsum(probs, axis=-1) - probs
        cutoff = jnp.maximum(
            jnp.sum(jnp.where(before < top_p, 1, 0), axis=-1, keepdims=True),
            1)
        thresh = jnp.take_along_axis(sort, cutoff - 1, axis=-1)
        logits = jnp.where(logits < thresh, NEG_INF, logits)
    return logits


@functools.partial(jax.jit, static_argnames=("vocab_size", "temperature",
                                             "top_k", "top_p"))
def sample_tokens(logits: jax.Array, key: jax.Array, vocab_size: int, *,
                  temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """Fused on-device stochastic sampler (temperature / top-k / top-p).

    Categorical sampling via the Gumbel trick on the filtered logits —
    an argmax the compiler fuses into the lm-head consumer exactly like
    ``greedy_sample``, so the fused decode round still pulls only a (B,)
    int32 vector.  ``temperature == 0`` degenerates to ``greedy_sample``
    (bit-identical argmax).  Padded vocab columns are masked before the
    filters, so a sampled id is always ``< vocab_size``.
    """
    if temperature == 0.0:
        return greedy_sample(logits, vocab_size)
    filt = _filtered_logits(logits, vocab_size, temperature, top_k, top_p)
    g = jax.random.gumbel(key, filt.shape, dtype=jnp.float32)
    tok = jnp.argmax(filt + g, axis=-1).astype(jnp.int32)
    return jnp.minimum(tok, vocab_size - 1)


@functools.partial(jax.jit, static_argnames=("vocab_size", "temperature",
                                             "top_k", "top_p", "greedy"))
def speculative_verify(
    target_logits: jax.Array,  # (B, k+1, V) — scores of [t0, d_1..d_k]
    draft_logits: jax.Array,   # (B, k, Vd) — draft scores that proposed d_j
    draft_tokens: jax.Array,   # (B, k) int32 — proposed tokens d_1..d_k
    key: jax.Array,
    vocab_size: int,
    *,
    temperature: float = 1.0,
    top_k: int = 0,
    top_p: float = 1.0,
    greedy: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """On-device speculative rejection sampling (Leviathan-style).

    Returns ``(out_tokens (B, k+1), n_accept (B,))``: the emitted token
    stream is ``out_tokens[:, :n_accept + 1]`` — the accepted draft prefix
    followed by one token drawn from the corrected residual distribution
    (or the bonus target sample when every draft was accepted).  Greedy
    mode (``greedy`` or ``temperature == 0``) accepts while the target
    argmax agrees with the draft, so draft == target yields the exact
    non-speculative greedy stream.  Both logit tensors are sliced to the
    shared real ``vocab_size`` so draft / target padding may differ.
    """
    b, kp1, _ = target_logits.shape
    k = kp1 - 1
    if greedy or temperature == 0.0:
        # argmax over the PADDED width + clip — exactly ``greedy_sample``
        # on the raw lm-head logits, so an accepted greedy stream is
        # bit-identical to the non-speculative fused path.
        g = greedy_sample(target_logits, vocab_size)            # (B, k+1)
        accept = (g[:, :k] == draft_tokens).astype(jnp.int32)   # (B, k)
        n_accept = jnp.cumprod(accept, axis=1).sum(axis=1)
        # accepted draft tokens equal the target argmax, so the emitted
        # stream *is* the target argmax over the window.
        return g, n_accept.astype(jnp.int32)
    tl = target_logits[..., :vocab_size]
    dl = draft_logits[..., :vocab_size]
    ukey, ckey = jax.random.split(key)
    p_t = jax.nn.softmax(
        _filtered_logits(tl, vocab_size, temperature, top_k, top_p), axis=-1)
    p_d = jax.nn.softmax(
        _filtered_logits(dl, vocab_size, temperature, top_k, top_p), axis=-1)
    d = draft_tokens[..., None]
    pt_d = jnp.take_along_axis(p_t[:, :k], d, axis=-1)[..., 0]  # (B, k)
    pd_d = jnp.take_along_axis(p_d, d, axis=-1)[..., 0]
    u = jax.random.uniform(ukey, (b, k), dtype=jnp.float32)
    accept = (u * pd_d < pt_d).astype(jnp.int32)
    n_accept = jnp.cumprod(accept, axis=1).sum(axis=1)          # (B,)
    # Residual distribution at the first rejected position; at position k
    # (all accepted) the draft contributes nothing and the residual is the
    # plain target distribution (bonus token).
    pad = jnp.zeros_like(p_t[:, :1])
    p_d_pad = jnp.concatenate([p_d, pad], axis=1)               # (B, k+1, V)
    at = n_accept[:, None, None]
    pt_at = jnp.take_along_axis(p_t, at, axis=1)[:, 0]          # (B, V)
    pd_at = jnp.take_along_axis(p_d_pad, at, axis=1)[:, 0]
    residual = jnp.maximum(pt_at - pd_at, 0.0)
    mass = residual.sum(axis=-1, keepdims=True)
    residual = jnp.where(mass > 0, residual, pt_at)
    logr = jnp.where(residual > 0, jnp.log(jnp.maximum(residual, 1e-30)),
                     NEG_INF)
    g = jax.random.gumbel(ckey, logr.shape, dtype=jnp.float32)
    corr = jnp.minimum(jnp.argmax(logr + g, axis=-1).astype(jnp.int32),
                       vocab_size - 1)                          # (B,)
    dpad = jnp.concatenate([draft_tokens, corr[:, None]], axis=1)
    out = jnp.where(jnp.arange(kp1)[None, :] < n_accept[:, None],
                    dpad, corr[:, None])
    return out, n_accept.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("window", "backend"))
def decode_attention(
    q: jax.Array,        # (B, 1, H, D) — one new token per sequence
    k_cache: jax.Array,  # (B, S, K, D)
    v_cache: jax.Array,  # (B, S, K, D)
    cache_len: jax.Array,  # (B,) int32 — valid prefix length (incl. new token)
    *,
    window: Optional[int] = None,
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """Single-token GQA attention against a (padded) KV cache."""
    if backend == "pallas":
        from repro.kernels import decode_attention as _da
        return _da.decode_attention_pallas(q, k_cache, v_cache, cache_len,
                                           window=window)
    if backend == "ref":
        return _ref.decode_reference(q, k_cache, v_cache, cache_len,
                                     window=window)
    b, _, h, d = q.shape
    _, s, n_kv, _ = k_cache.shape
    scale = d ** -0.5
    qg = _gqa_expand(q, n_kv).astype(jnp.float32) * scale  # (B,1,K,G,D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k_cache.astype(jnp.float32))  # (B,K,G,1,S)
    pos = jnp.arange(s)
    valid = pos[None, :] < cache_len[:, None]  # (B, S)
    if window is not None:
        valid &= pos[None, :] > cache_len[:, None] - 1 - window
    scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("backend",))
def verify_attention(
    q: jax.Array,          # (B, W, H, D) — W window tokens per sequence
    k_cache: jax.Array,    # (B, S, K, D) with rows pos..pos+W-1 written
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) int32 — valid rows *before* the window
    *,
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """Multi-token verify attention for speculative decoding.

    ``decode_attention``'s (B, S) validity mask is shared by all query
    rows, which is wrong for W > 1: window query ``j`` (absolute position
    ``cache_len + j``) may attend rows ``< cache_len + j + 1`` only —
    earlier draft rows plus itself, never later ones.  Same einsum layout
    as the decode path with a per-query-row (B, W, S) mask.
    """
    b, w, h, d = q.shape
    _, s, n_kv, _ = k_cache.shape
    scale = d ** -0.5
    qg = _gqa_expand(q, n_kv).astype(jnp.float32) * scale  # (B,W,K,G,D)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg,
                        k_cache.astype(jnp.float32))  # (B,K,G,W,S)
    pos = jnp.arange(s)
    valid = (pos[None, None, :] <
             cache_len[:, None, None] + jnp.arange(w)[None, :, None] + 1)
    scores = jnp.where(valid[:, None, None, :, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, w, h, d).astype(q.dtype)


def _gather_pages(pages: jax.Array, block_tables: jax.Array,
                  layer: Optional[jax.Array] = None) -> jax.Array:
    """(N, bs, K, D) physical pages + (B, M) block table -> contiguous
    (B, M*bs, K, D) caches in logical order (reference materialization).
    Given ``layer``, ``pages`` is the stacked (L, N, bs, K, D) pool and the
    one gather ``pages[layer, block_tables]`` reads it in place: no layer
    is sliced out first."""
    b, m = block_tables.shape
    g = pages[block_tables] if layer is None else pages[layer, block_tables]
    return g.reshape(b, m * g.shape[2], *g.shape[3:])  # from (B, M, bs, ...)


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_decode_attention(
    q: jax.Array,             # (B, 1, H, D) — one new token per sequence
    k_pages: jax.Array,       # (N, bs, K, D) physical KV blocks
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, M) int32 — physical block per logical slot
    cache_len: jax.Array,     # (B,) int32 — valid prefix length
    *,
    layer: Optional[jax.Array] = None,  # () int32: pages are (L, N, ...)
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """Single-token GQA attention against a block-paged KV cache.

    The pallas backend walks the block table with scalar-prefetch index
    maps, streaming only each sequence's own blocks from HBM; the xla/ref
    fallback materializes the gather and reuses ``decode_attention``.
    Padded table entries (the null block) are masked by ``cache_len``.
    With ``layer``, both read that layer of stacked pools in place.
    """
    if backend == "pallas":
        from repro.kernels import decode_attention as _da
        return _da.paged_decode_attention_pallas(q, k_pages, v_pages,
                                                 block_tables, cache_len,
                                                 layer)
    k = _gather_pages(k_pages, block_tables, layer)
    v = _gather_pages(v_pages, block_tables, layer)
    return decode_attention(q, k, v, cache_len, backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_verify_attention(
    q: jax.Array,             # (B, W, H, D)
    k_pages: jax.Array,       # (N, bs, K, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, M) int32
    cache_len: jax.Array,     # (B,) int32 — valid rows before the window
    *,
    layer: Optional[jax.Array] = None,  # () int32: pages are (L, N, ...)
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """``verify_attention`` against a block-paged KV cache (gather
    materialization, same per-query-row causal mask)."""
    k = _gather_pages(k_pages, block_tables, layer)
    v = _gather_pages(v_pages, block_tables, layer)
    return verify_attention(q, k, v, cache_len, backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def paged_decode_attention_quant(
    q: jax.Array,             # (B, 1, H, D)
    k_pages: jax.Array,       # (N, bs, K, D) int8 codes
    v_pages: jax.Array,
    k_scale: jax.Array,       # (N, bs, K, 1) bf16 per-(pos, kv-head) scales
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, M) int32
    cache_len: jax.Array,     # (B,) int32
    *,
    layer: Optional[jax.Array] = None,  # () int32: pages are (L, N, ...)
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """Paged decode attention over int8 KV blocks (§Perf D x paging)."""
    if backend == "pallas":
        from repro.kernels import decode_attention as _da
        return _da.paged_decode_attention_quant_pallas(
            q, k_pages, v_pages, k_scale, v_scale, block_tables, cache_len,
            layer)
    return decode_attention_quant(
        q, _gather_pages(k_pages, block_tables, layer),
        _gather_pages(v_pages, block_tables, layer),
        _gather_pages(k_scale, block_tables, layer),
        _gather_pages(v_scale, block_tables, layer),
        cache_len, backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def decode_attention_quant(
    q: jax.Array,        # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, K, D) int8 codes
    v_cache: jax.Array,
    k_scale: jax.Array,  # (B, S, K, 1) bf16 per-(pos, kv-head) scales
    v_scale: jax.Array,
    cache_len: jax.Array,
    *,
    backend: str = DEFAULT_BACKEND,
) -> jax.Array:
    """Decode attention over an int8 KV cache (§Perf D).

    The pallas backend streams int8 + scales and dequantizes in VMEM; the
    xla/ref backends dequantize then reuse the bf16 path (on CPU the
    dequant fuses into the consumer, so HBM reads stay int8-sized).
    """
    if backend == "pallas":
        from repro.kernels import decode_attention as _da
        return _da.decode_attention_quant_pallas(q, k_cache, v_cache,
                                                 k_scale, v_scale, cache_len)

    def deq(c, s):
        return (c.astype(jnp.float32) * s.astype(jnp.float32)).astype(
            jnp.bfloat16)

    return decode_attention(q, deq(k_cache, k_scale), deq(v_cache, v_scale),
                            cache_len, backend=backend)


@functools.partial(jax.jit, static_argnames=("backend",))
def wkv6_scan(
    r: jax.Array,  # (B, S, H, D) receptance
    k: jax.Array,  # (B, S, H, D)
    v: jax.Array,  # (B, S, H, D)
    w: jax.Array,  # (B, S, H, D) data-dependent decay (log-space, negative)
    u: jax.Array,  # (H, D) bonus for current token
    state: jax.Array,  # (B, H, D, D) recurrent state
    *,
    backend: str = DEFAULT_BACKEND,
) -> tuple[jax.Array, jax.Array]:
    """RWKV-6 WKV recurrence. Returns (out (B,S,H,D), new state)."""
    if backend == "pallas":
        _refuse_on_tpu("wkv6_scan")
        from repro.kernels import wkv6 as _wkv
        return _wkv.wkv6_pallas(r, k, v, w, u, state)
    if backend == "ref":
        return _ref.wkv6_reference(r, k, v, w, u, state)
    # xla path: lax.scan over time (compact HLO; sequential like the kernel).
    rf, kf, vf, wf = (x.astype(jnp.float32).transpose(1, 0, 2, 3)
                      for x in (r, k, v, w))

    def step(s, inputs):  # s: (B, H, D, D) maps k-dim x v-dim
        rt, kt, vt, wt = inputs  # each (B, H, D)
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,Dk,Dv)
        # out_t = r . (u*kv + state)
        att = s + u.astype(jnp.float32)[None, :, :, None] * kv
        out = jnp.einsum("bhk,bhkv->bhv", rt, att)
        s_new = jnp.exp(wt)[..., None] * s + kv
        return s_new, out

    state_f, outs = jax.lax.scan(step, state.astype(jnp.float32),
                                 (rf, kf, vf, wf))
    return outs.transpose(1, 0, 2, 3).astype(r.dtype), state_f.astype(state.dtype)


@functools.partial(jax.jit, static_argnames=("backend",))
def ssm_scan(
    x: jax.Array,      # (B, S, H, D) input per head
    dt: jax.Array,     # (B, S, H) step size (post-softplus)
    a_log: jax.Array,  # (H, N) state matrix (log of -A)
    b: jax.Array,      # (B, S, H, N) input matrix
    c: jax.Array,      # (B, S, H, N) output matrix
    state: jax.Array,  # (B, H, D, N)
    *,
    backend: str = DEFAULT_BACKEND,
) -> tuple[jax.Array, jax.Array]:
    """Mamba-style selective scan (Hymba SSM heads)."""
    if backend == "pallas":
        _refuse_on_tpu("ssm_scan")
        from repro.kernels import ssm_scan as _ssm
        return _ssm.ssm_scan_pallas(x, dt, a_log, b, c, state)
    if backend == "ref":
        return _ref.ssm_reference(x, dt, a_log, b, c, state)
    a = -jnp.exp(a_log.astype(jnp.float32))  # (H, N)
    xf = x.astype(jnp.float32).transpose(1, 0, 2, 3)   # (S,B,H,D)
    dtf = dt.astype(jnp.float32).transpose(1, 0, 2)    # (S,B,H)
    bf = b.astype(jnp.float32).transpose(1, 0, 2, 3)   # (S,B,H,N)
    cf = c.astype(jnp.float32).transpose(1, 0, 2, 3)

    def step(s, inputs):  # s: (B,H,D,N)
        xt, dtt, bt, ct = inputs
        da = jnp.exp(dtt[..., None] * a[None])          # (B,H,N)
        dbx = (dtt[..., None] * bt)[:, :, None, :] * xt[..., None]  # (B,H,D,N)
        s_new = da[:, :, None, :] * s + dbx
        yt = jnp.einsum("bhdn,bhn->bhd", s_new, ct)
        return s_new, yt

    state_f, ys = jax.lax.scan(step, state.astype(jnp.float32),
                               (xf, dtf, bf, cf))
    return ys.transpose(1, 0, 2, 3).astype(x.dtype), state_f.astype(state.dtype)
