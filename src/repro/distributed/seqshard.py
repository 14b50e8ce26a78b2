"""Sequence-sharded paged decode: all-gather-free attention over block slabs.

``cache_pspec`` falls back to sharding the *sequence* axis when neither
batch nor kv-heads divide the mesh (batch=1 long-context decode, GQA with
kv < TP).  For the paged plane that means each device owns a contiguous
slab of physical KV blocks, and a decode step must attend all of them —
flash-decoding style: every device computes a *partial* softmax over its
local blocks and the partials merge with one log-sum-exp combine
(``softmax_combine``), two tiny collectives instead of all-gathering the
KV itself.

This seam is opt-in: the engine's default cache placement shards kv-heads
and replicates when they don't divide (bitwise-safe — no cross-device
reduction touches the logits), so ``paged_decode_attention_seqshard``
exists for the configs whose KV genuinely cannot fit replicated.  It is
numerically equivalent (f32 accumulation, ~1 ulp reassociation) to
``kernels.ops.paged_decode_attention``, not bit-identical — exactly the
trade the docstring of ``cache_pspec`` promises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # finite, like attention.py: exp(NEG_INF - m) underflows to 0


def softmax_combine(num: jax.Array, m: jax.Array, den: jax.Array,
                    axis: str) -> jax.Array:
    """Merge per-shard partial softmaxes with one log-sum-exp rescale.

    ``num``: unnormalized weighted-value partials ``(..., D)``;
    ``m``: per-shard row maxima ``(...)``; ``den``: per-shard partition
    sums ``(...)``, all computed against the shard-local keys only.
    Returns the globally-normalized attention output — identical (up to
    f32 reassociation) to a softmax over the concatenated keys.
    """
    m_glob = jax.lax.pmax(m, axis)
    scale = jnp.exp(m - m_glob)
    total_num = jax.lax.psum(num * scale[..., None], axis)
    total_den = jax.lax.psum(den * scale, axis)
    return total_num / jnp.maximum(total_den, 1e-30)[..., None]


def _local_partials(q: jax.Array, k_loc: jax.Array, v_loc: jax.Array,
                    block_tables: jax.Array, cache_len: jax.Array,
                    shard: jax.Array
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Partial attention of ``q`` against this shard's block slab.

    ``k_loc``/``v_loc``: (N_local, bs, K, Dh) — the shard's slab; global
    block ``t`` lives here iff ``t // N_local == shard``.  Rows of
    ``block_tables`` pointing off-shard (or past ``cache_len``) are
    masked, so each device scores only the tokens it physically holds.
    Returns (num (B,K,G,Dh), m (B,K,G), den (B,K,G)) in f32.
    """
    b, _, h, dh = q.shape
    n_loc, bs, kv, _ = k_loc.shape
    g = h // kv
    qf = q.astype(jnp.float32).reshape(b, kv, g, dh) * dh ** -0.5

    t = block_tables  # (B, M) global block ids
    owned = (t >= shard * n_loc) & (t < (shard + 1) * n_loc)
    local = jnp.clip(t - shard * n_loc, 0, n_loc - 1)
    k_g = jnp.take(k_loc, local, axis=0).astype(jnp.float32)  # (B,M,bs,K,Dh)
    v_g = jnp.take(v_loc, local, axis=0).astype(jnp.float32)

    scores = jnp.einsum("bkgd,bmskd->bkgms", qf, k_g)  # (B,K,G,M,bs)
    pos_tok = (jnp.arange(t.shape[1])[:, None] * bs
               + jnp.arange(bs)[None, :])  # (M, bs)
    valid = (owned[:, :, None]
             & (pos_tok[None] < cache_len[:, None, None]))  # (B,M,bs)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    m = scores.max(axis=(-2, -1))  # (B,K,G)
    # NEG_INF is finite: an all-masked shard has m == NEG_INF and every
    # exp() == 1, so the valid mask must gate the weights, not the scores.
    p = jnp.exp(scores - m[..., None, None]) * valid[:, None, None]
    den = p.sum(axis=(-2, -1))
    num = jnp.einsum("bkgms,bmskd->bkgd", p, v_g)
    return num, m, den


def paged_decode_attention_seqshard(q: jax.Array, k_pages: jax.Array,
                                    v_pages: jax.Array,
                                    block_tables: jax.Array,
                                    cache_len: jax.Array,
                                    mesh: Mesh,
                                    axis: str = "model") -> jax.Array:
    """``ops.paged_decode_attention`` with the page pool sharded over
    ``axis`` on the physical-block dimension.

    q: (B, 1, H, Dh); k_pages/v_pages: (N, bs, K, Dh) with
    ``N % mesh.shape[axis] == 0``; block_tables: (B, M) int32;
    cache_len: (B,) int32.  Returns (B, 1, H, Dh).
    """
    n_blocks = k_pages.shape[0]
    tp = int(mesh.shape[axis])
    if n_blocks % tp != 0:
        raise ValueError(
            f"n_blocks={n_blocks} must divide over {axis}={tp} to "
            f"sequence-shard the page pool")
    b, _, h, dh = q.shape

    def body(ql, kl, vl, tables, lens):
        shard = jax.lax.axis_index(axis)
        num, m, den = _local_partials(ql, kl, vl, tables, lens, shard)
        out = softmax_combine(num, m, den, axis)  # (B,K,G,Dh)
        return out.reshape(b, 1, h, dh).astype(q.dtype)

    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(), P()),
        out_specs=P(), check_vma=False)
    return fn(q, k_pages, v_pages, block_tables, cache_len)
