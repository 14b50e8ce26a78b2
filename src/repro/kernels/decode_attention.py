"""Single-token GQA decode attention Pallas TPU kernels (dense and paged).

The serving hot-spot: one query token per sequence attends over a long
KV cache.  Grid = (batch, kv-blocks); one grid step streams a
(block, K, d) tile — every KV head of ``block`` consecutive cache rows —
and the G query heads of each kv group are processed together as a
(G x d) tile, so the MXU sees a real matmul instead of G matvecs.  The
block keeps the whole KV-head axis because Mosaic tiles the last two
dims of a block: (K, d) is legal as the array's own trailing dims, a
single head (1, d) is not.  Online softmax state lives in VMEM scratch
across the sequential kv-block dimension; per-row cache lengths (and the
paged block tables) arrive as scalar-prefetch operands in SMEM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(*refs, n_pre: int, quant: bool, block_s: int, n_blocks: int,
            n_kv: int, window: Optional[int], scale: float):
    """One kv-block step for every KV head of one sequence.

    ``refs`` = ``n_pre`` scalar prefetch operands (``len_ref`` [,
    ``tbl_ref`` [, ``layer_ref``]]), then q, k, v [, k_scale, v_scale],
    the output, and the acc / m / l scratch.  Paged
    grids walk LOGICAL block slots: the physical page each step streams
    was picked by the K/V index map from ``tbl_ref``, so only a
    sequence's own blocks leave HBM; past-the-end slots point at the null
    block and are masked by ``cache_len`` exactly like dense padding.
    """
    len_ref = refs[0]
    q_ref, k_ref, v_ref = refs[n_pre:n_pre + 3]
    ks_ref, vs_ref = refs[n_pre + 3:n_pre + 5] if quant else (None, None)
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    cache_len = len_ref[ib]
    blk_lo = ik * block_s
    live = blk_lo < cache_len
    if window is not None:
        live &= (blk_lo + block_s) > cache_len - 1 - window

    @pl.when(live)
    def _compute():
        for h in range(n_kv):
            q = q_ref[h].astype(jnp.float32) * scale  # (G, d)
            k = k_ref[:, h, :].astype(jnp.float32)  # (bs, d)
            v = v_ref[:, h, :].astype(jnp.float32)
            if quant:  # int8 codes dequantize in VMEM after the HBM load
                k = k * ks_ref[:, h, :].astype(jnp.float32)  # (bs, 1)
                v = v * vs_ref[:, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, bs)
            pos = blk_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = pos < cache_len
            if window is not None:
                mask &= pos > cache_len - 1 - window
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[h]  # (G, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())))
            m_ref[h] = m_new

    @pl.when(ik == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_call(q: jax.Array, kv: tuple, cache_len: jax.Array,
                 block_tables: Optional[jax.Array], *, block_s: int,
                 n_blocks: int, window: Optional[int],
                 interpret: Optional[bool],
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """Shared ``pallas_call`` of the four decode variants.

    ``kv`` is (k, v) or (k, v, k_scale, v_scale) — dense (B, S, K, ...)
    caches when ``block_tables`` is None, else (N, bs, K, ...) pages, or
    stacked (L, N, bs, K, ...) pools read at ``layer``.
    """
    b, _, h, d = q.shape
    n_kv = kv[0].shape[-2]
    g = h // n_kv
    lead = (None,)
    if block_tables is None:
        def kv_index(ib, ik, len_ref):
            return ib, ik, 0, 0
        prefetch = (cache_len.astype(jnp.int32),)
    elif layer is None:
        def kv_index(ib, ik, len_ref, tbl_ref):
            return tbl_ref[ib, ik], 0, 0, 0
        prefetch = (cache_len.astype(jnp.int32),
                    block_tables.astype(jnp.int32))
    else:
        def kv_index(ib, ik, len_ref, tbl_ref, layer_ref):
            return layer_ref[0], tbl_ref[ib, ik], 0, 0, 0
        prefetch = (cache_len.astype(jnp.int32),
                    block_tables.astype(jnp.int32),
                    jnp.reshape(layer, (1,)).astype(jnp.int32))
        lead = (None, None)
    kernel = functools.partial(
        _kernel, n_pre=len(prefetch), quant=len(kv) == 4, block_s=block_s,
        n_blocks=n_blocks, n_kv=n_kv, window=window, scale=d ** -0.5)
    kv_specs = [pl.BlockSpec((*lead, block_s, n_kv, x.shape[-1]), kv_index)
                for x in kv]
    head_spec = pl.BlockSpec((None, n_kv, g, d), lambda ib, ik, *_:
                             (ib, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, n_blocks),
        in_specs=[head_spec, *kv_specs],
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((n_kv, g, d), jnp.float32),
            pltpu.VMEM((n_kv, g, 1), jnp.float32),
            pltpu.VMEM((n_kv, g, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(*prefetch, q.reshape(b, n_kv, g, d), *kv)
    return out.reshape(b, 1, h, d)


def _dense_blocks(s: int, block_s: int) -> tuple[int, int]:
    block_s = min(block_s, s)
    if s % block_s:
        raise ValueError("cache length must divide block_s")
    return block_s, s // block_s


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(
    q: jax.Array,             # (B, 1, H, D)
    k_pages: jax.Array,       # (N, bs, K, D) physical KV blocks
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, M) int32
    cache_len: jax.Array,     # (B,) int32
    layer: Optional[jax.Array] = None,  # () int32: pages are (L, N, ...)
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Paged single-token GQA decode: grid = (batch, table slot).

    ``block_tables`` and ``cache_len`` (and ``layer``, for stacked pools)
    ride in as scalar-prefetch operands (``pltpu.PrefetchScalarGridSpec``)
    so the K/V index maps can pick the PHYSICAL page for each logical slot
    before the DMA is issued — the TPU-native equivalent of vLLM's paged
    attention.
    """
    return _decode_call(q, (k_pages, v_pages), cache_len, block_tables,
                        block_s=k_pages.shape[-3],
                        n_blocks=block_tables.shape[1], window=None,
                        interpret=interpret, layer=layer)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_quant_pallas(
    q: jax.Array,             # (B, 1, H, D)
    k_pages: jax.Array,       # (N, bs, K, D) int8 codes
    v_pages: jax.Array,
    k_scale: jax.Array,       # (N, bs, K, 1) bf16 scales
    v_scale: jax.Array,
    block_tables: jax.Array,  # (B, M) int32
    cache_len: jax.Array,     # (B,) int32
    layer: Optional[jax.Array] = None,  # () int32: pages are (L, N, ...)
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """int8-KV paged variant: codes + per-row scales stream per physical
    block and dequantize in VMEM (1 byte/element over the wire)."""
    return _decode_call(q, (k_pages, v_pages, k_scale, v_scale), cache_len,
                        block_tables, block_s=k_pages.shape[-3],
                        n_blocks=block_tables.shape[1], window=None,
                        interpret=interpret, layer=layer)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_quant_pallas(
    q: jax.Array,        # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, K, D) int8
    v_cache: jax.Array,  # (B, S, K, D) int8
    k_scale: jax.Array,  # (B, S, K, 1) bf16
    v_scale: jax.Array,
    cache_len: jax.Array,  # (B,) int32
    *,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    block_s, ns = _dense_blocks(k_cache.shape[1], block_s)
    return _decode_call(q, (k_cache, v_cache, k_scale, v_scale), cache_len,
                        None, block_s=block_s, n_blocks=ns, window=None,
                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention_pallas(
    q: jax.Array,        # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, K, D)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) int32
    *,
    window: Optional[int] = None,
    block_s: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    block_s, ns = _dense_blocks(k_cache.shape[1], block_s)
    return _decode_call(q, (k_cache, v_cache), cache_len, None,
                        block_s=block_s, n_blocks=ns, window=window,
                        interpret=interpret)
