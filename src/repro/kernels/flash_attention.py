"""FlashAttention Pallas TPU kernel (GQA, causal, sliding window).

TPU-native adaptation (DESIGN.md): the grid is (batch, q-head, q-blocks,
kv-blocks) with the kv dimension marked "arbitrary" (sequential) so the
online-softmax running state lives in VMEM scratch across kv steps — the
HBM->VMEM pipeline streams k/v blocks while the MXU consumes the previous
one.  Block shapes are (block_q x d_head) / (block_k x d_head) tiles,
MXU-aligned when block sizes are multiples of 128.

Causal/sliding-window masking is applied per element; fully-masked kv
blocks are skipped with ``pl.when`` so the causal lower triangle costs
~half the full-attention FLOPs.

Validated on CPU in interpret mode against ``ref.mha_reference``. The v5e
compiler refuses it: a block streams one head, (1, d) over the array's (H,
d) trailing dims, which Mosaic cannot tile; ``ops`` raises on a TPU instead.
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


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            causal: bool, window: Optional[int], q_offset: int,
            block_q: int, block_k: int, n_kv_blocks: int, scale: float):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # Skip kv blocks that are entirely masked out.
    q_lo = q_offset + iq * block_q
    q_hi = q_lo + block_q - 1
    k_lo = ik * block_k
    k_hi = k_lo + block_k - 1
    live = jnp.asarray(True)
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale  # (bq, d)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "block_q", "block_k",
                     "interpret"))
def flash_attention_pallas(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Sk, K, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jax.Array:
    b, sq, h, d = q.shape
    _, sk, n_kv, _ = k.shape
    g = h // n_kv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError("sequence lengths must divide block sizes")
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _kernel, causal=causal, window=window, q_offset=q_offset,
        block_q=block_q, block_k=block_k, n_kv_blocks=nk, scale=d ** -0.5)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d),
                         lambda ib, ih, iq, ik: (ib, iq, ih, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda ib, ih, iq, ik, g=g: (ib, ik, ih // g, 0)),
            pl.BlockSpec((1, block_k, 1, d),
                         lambda ib, ih, iq, ik, g=g: (ib, ik, ih // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, d),
                               lambda ib, ih, iq, ik: (ib, iq, ih, 0)),
        out_shape=jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(q, k, v)
