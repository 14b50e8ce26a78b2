"""Mamba-style selective scan Pallas TPU kernel (Hymba SSM heads).

Grid = (batch, head, time-chunks) with the (D x N) state in VMEM scratch
across the sequential time axis.  Per step: elementwise decay
``exp(dt * A)`` on the (1 x N) row, a rank-1 (D x N) state update, and a
(D x N) x (N,) contraction for the output — all VPU-friendly shapes.

Validated in interpret mode against ``ref.ssm_reference``. The v5e compiler
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


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, s0_ref, y_ref, sT_ref,
            state_ref, *, block_t: int, n_blocks: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, :, 0, :].astype(jnp.float32)    # (bt, d)
    dt = dt_ref[0, :, 0].astype(jnp.float32)     # (bt,)
    a = -jnp.exp(a_ref[0].astype(jnp.float32))   # (n,)
    bm = b_ref[0, :, 0, :].astype(jnp.float32)   # (bt, n)
    cm = c_ref[0, :, 0, :].astype(jnp.float32)   # (bt, n)

    def step(t, _):
        xt = jax.lax.dynamic_slice_in_dim(x, t, 1, 0)    # (1, d)
        dtt = jax.lax.dynamic_slice_in_dim(dt, t, 1, 0)  # (1,)
        bt_ = jax.lax.dynamic_slice_in_dim(bm, t, 1, 0)  # (1, n)
        ct = jax.lax.dynamic_slice_in_dim(cm, t, 1, 0)   # (1, n)
        da = jnp.exp(dtt[0] * a)  # (n,)
        dbx = xt.T @ (dtt[0] * bt_)  # (d, n) rank-1
        state_ref[...] = state_ref[...] * da[None, :] + dbx
        y = state_ref[...] @ ct[0][:, None]  # (d, 1)
        y_ref[0, t, 0, :] = y[:, 0].astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, block_t, step, ())

    @pl.when(it == n_blocks - 1)
    def _finalize():
        sT_ref[0, 0] = state_ref[...].astype(sT_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def ssm_scan_pallas(
    x: jax.Array,      # (B, S, H, D)
    dt: jax.Array,     # (B, S, H)
    a_log: jax.Array,  # (H, N)
    b: jax.Array,      # (B, S, H, N)
    c: jax.Array,      # (B, S, H, N)
    state: jax.Array,  # (B, H, D, N)
    *,
    block_t: int = 256,
    interpret: Optional[bool] = None,
) -> tuple[jax.Array, jax.Array]:
    bsz, s, h, d = x.shape
    n = a_log.shape[-1]
    block_t = min(block_t, s)
    if s % block_t:
        raise ValueError("sequence length must divide block_t")
    nt = s // block_t
    kernel = functools.partial(_kernel, block_t=block_t, n_blocks=nt)

    y, s_t = pl.pallas_call(
        kernel,
        grid=(bsz, h, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, 1, d), lambda ib, ih, it: (ib, it, ih, 0)),
            pl.BlockSpec((1, block_t, 1), lambda ib, ih, it: (ib, it, ih)),
            pl.BlockSpec((1, n), lambda ib, ih, it: (ih, 0)),
            pl.BlockSpec((1, block_t, 1, n), lambda ib, ih, it: (ib, it, ih, 0)),
            pl.BlockSpec((1, block_t, 1, n), lambda ib, ih, it: (ib, it, ih, 0)),
            pl.BlockSpec((1, 1, d, n), lambda ib, ih, it: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, 1, d), lambda ib, ih, it: (ib, it, ih, 0)),
            pl.BlockSpec((1, 1, d, n), lambda ib, ih, it: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s, h, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, d, n), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(x, dt, a_log, b, c, state)
    return y, s_t
