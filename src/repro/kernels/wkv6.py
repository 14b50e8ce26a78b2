"""RWKV-6 WKV recurrence Pallas TPU kernel.

Grid = (batch, head, time-chunks); the (Dk x Dv) recurrent state lives in
VMEM scratch across the sequential time dimension, so HBM traffic is one
read of r/k/v/w and one write of the output per token — the recurrence
itself never round-trips state through HBM.  Inside a chunk the timestep
loop is a ``fori_loop`` over VMEM-resident tiles: each step is a (1 x D) x
(D x D) matvec plus two rank-1 updates, which the VPU/MXU handle natively —
this replaces the CUDA warp-per-channel formulation of the reference
implementation (DESIGN.md: hardware adaptation).

Validated in interpret mode against ``ref.wkv6_reference``. The v5e compiler
refuses it: a block streams one head, (1, d) over the array's (H, d)
trailing dims, which Mosaic cannot tile; ``ops`` raises on a TPU instead.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            state_ref, *, block_t: int, n_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0, :].astype(jnp.float32)  # (bt, d)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)  # (d,)

    def step(t, _):
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)  # (1, d)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = kt.T @ vt  # (dk, dv) rank-1
        att = state_ref[...] + u[:, None] * kv
        out = rt @ att  # (1, dv)
        o_ref[0, t, 0, :] = out[0].astype(o_ref.dtype)
        state_ref[...] = jnp.exp(wt[0])[:, None] * state_ref[...] + kv
        return ()

    jax.lax.fori_loop(0, block_t, step, ())

    @pl.when(it == n_blocks - 1)
    def _finalize():
        sT_ref[0, 0] = state_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def wkv6_pallas(
    r: jax.Array,  # (B, S, H, D)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,  # log-space decay (negative)
    u: jax.Array,  # (H, D)
    state: jax.Array,  # (B, H, D, D)
    *,
    block_t: int = 256,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    b, s, h, d = r.shape
    block_t = min(block_t, s)
    if s % block_t:
        raise ValueError("sequence length must divide block_t")
    nt = s // block_t
    kernel = functools.partial(_kernel, block_t=block_t, n_blocks=nt)

    seq_spec = pl.BlockSpec((1, block_t, 1, d),
                            lambda ib, ih, it: (ib, it, ih, 0))
    out, s_t = pl.pallas_call(
        kernel,
        grid=(b, h, nt),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, d), lambda ib, ih, it: (ih, 0)),
            pl.BlockSpec((1, 1, d, d), lambda ib, ih, it: (ib, ih, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, d, d), lambda ib, ih, it: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, h, d), r.dtype),
            jax.ShapeDtypeStruct((b, h, d, d), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(r, k, v, w, u, state)
    return out, s_t
