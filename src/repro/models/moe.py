"""Mixture-of-Experts block: top-k routing with sort-based dispatch.

Design (DESIGN.md §5): expert weights are *not* sharded over an expert axis;
each expert's matrices shard 2D over (fsdp=data, tp=model) like a dense MLP.
Routing is therefore all-to-all-free: tokens are sorted by expert id,
gathered into per-expert capacity buckets, pushed through a batched
(E, C, D) x (E, D, F) einsum, and combined back with their gate weights.
Overflow beyond capacity is dropped (standard capacity-factor semantics);
the router's load-balancing auxiliary loss keeps drops rare in training.

The baseline lowers under auto-SPMD (XLA inserts the collectives around the
global argsort); the §Perf hillclimb replaces this with shard_map-local
routing and measures the difference.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import current_mesh, named
from repro.models.config import ModelConfig
from repro.models.layers import PSpec


def moe_specs(cfg: ModelConfig) -> dict[str, PSpec]:
    d, fe, e = cfg.d_model, cfg.expert_d_ff, cfg.n_experts
    s = {
        "router": PSpec((d, e), ("fsdp", None), dtype=jnp.float32),
        "w_gate": PSpec((e, d, fe), (None, "fsdp", "tp")),
        "w_up": PSpec((e, d, fe), (None, "fsdp", "tp")),
        "w_down": PSpec((e, fe, d), (None, "tp", "fsdp")),
    }
    if cfg.n_shared_experts:
        fs = cfg.n_shared_experts * fe
        s["shared"] = {
            "w_gate": PSpec((d, fs), ("fsdp", "tp")),
            "w_up": PSpec((d, fs), ("fsdp", "tp")),
            "w_down": PSpec((fs, d), ("tp", "fsdp")),
            "gate": PSpec((d, 1), ("fsdp", None)),
        }
    return s


def _capacity(n_tokens: int, cfg: ModelConfig, factor: float) -> int:
    c = int(n_tokens * cfg.top_k * factor / cfg.n_experts) + 1
    # One expert can receive at most one pair per token (top-k experts are
    # distinct), so capacity beyond n_tokens is dead rows.  Clamping is
    # lossless and matters on the decode hot path: a B-slot decode round
    # has n_tokens == B, and without the clamp every expert bucket pads to
    # the training floor of 8 — 2-4x wasted expert-FFN FLOPs per round.
    return min(max(c, cfg.top_k, 8), max(n_tokens, 1))


@dataclasses.dataclass
class MoEStats:
    aux_loss: jax.Array  # load-balancing loss (Switch-style)


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig,
              capacity_factor: float = 1.25
              ) -> tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,D), aux_loss scalar).

    With an active mesh this dispatches to the shard_map-local path
    (§Perf iteration B1): tokens are routed entirely within their data
    shard — no global argsort/scatter collectives — and the only wire
    traffic left is the per-layer FSDP weight gather plus one TP psum of
    the combined output, exactly like a dense MLP.
    """
    import os
    mesh = current_mesh()
    if (mesh is not None and "model" in mesh.shape
            and os.environ.get("REPRO_BASELINE", "") != "1"):
        dp = tuple(a for a in ("pod", "data") if a in mesh.shape)
        b = x.shape[0]
        import math as _math
        if dp and b % _math.prod(mesh.shape[a] for a in dp) == 0:
            return _moe_apply_shardmap(params, x, cfg, capacity_factor,
                                       mesh, dp)
    return _moe_apply_global(params, x, cfg, capacity_factor)


def _moe_local(router, w_gate, w_up, w_down, shared, xt, cfg: ModelConfig,
               cap: int) -> tuple[jax.Array, jax.Array]:
    """Route + compute experts for the local token slab ``xt`` (T, D).

    Expert FFN dims may be TP shards; the caller psums the partial output.
    """
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k

    logits = (xt.astype(jnp.float32) @ router)  # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    me = gates.mean(axis=0)
    ce = jnp.zeros((e,)).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    pair_e = top_e.reshape(-1)
    pair_tok = jnp.repeat(jnp.arange(t), k)
    pair_w = top_w.reshape(-1)
    order = jnp.argsort(pair_e, stable=True)
    pe, ptok, pw = pair_e[order], pair_tok[order], pair_w[order]
    counts = jnp.zeros((e,), jnp.int32).at[pe].add(1)
    offsets = jnp.cumsum(counts) - counts
    within = jnp.arange(t * k) - offsets[pe]
    keep = within < cap
    dest = jnp.where(keep, pe * cap + within, e * cap)

    buckets = jnp.zeros((e * cap + 1, d), xt.dtype).at[dest].set(xt[ptok])
    expert_in = buckets[:-1].reshape(e, cap, d)

    h_gate = jnp.einsum("ecd,edf->ecf", expert_in, w_gate)
    h_up = jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(xt.dtype) * h_up
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)

    flat = jnp.concatenate(
        [expert_out.reshape(e * cap, d),
         jnp.zeros((1, d), expert_out.dtype)], axis=0)
    pair_out = flat[dest] * (pw * keep).astype(xt.dtype)[:, None]
    y = jnp.zeros((t, d), xt.dtype).at[ptok].add(pair_out)

    if shared is not None:
        sw_gate, sw_up, sw_down, sgate = shared
        g = jax.nn.silu((xt @ sw_gate).astype(jnp.float32)).astype(xt.dtype)
        hs = g * (xt @ sw_up)
        shared_out = hs @ sw_down
        mix = jax.nn.sigmoid((xt.astype(jnp.float32) @ sgate))
        y = y + shared_out * mix.astype(xt.dtype)
    return y, aux


def _moe_apply_shardmap(params: dict, x: jax.Array, cfg: ModelConfig,
                        capacity_factor: float, mesh, dp: tuple
                        ) -> tuple[jax.Array, jax.Array]:
    """shard_map-local routing: data-parallel token slabs, TP expert FFNs."""
    import math as _math
    b, s, d = x.shape
    n_dp = _math.prod(mesh.shape[a] for a in dp)
    t_local = (b // n_dp) * s
    cap = _capacity(t_local, cfg, capacity_factor)
    has_shared = "shared" in params
    dp_spec = dp if len(dp) > 1 else dp[0]

    def local_fn(xl, router, w_gate, w_up, w_down, *shared_args):
        xt = xl.reshape(-1, d)
        shared = shared_args if has_shared else None
        y, aux = _moe_local(router, w_gate, w_up, w_down, shared, xt, cfg,
                            cap)
        # Expert/shared FFN dims are TP shards -> partial sums; one psum
        # combines routed + shared contributions (the dense-MLP pattern).
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return y.reshape(xl.shape), aux

    in_specs = [P(dp_spec, None, None), P(None, None),
                P(None, None, "model"), P(None, None, "model"),
                P(None, "model", None)]
    args = [x, params["router"], params["w_gate"], params["w_up"],
            params["w_down"]]
    if has_shared:
        sp = params["shared"]
        args += [sp["w_gate"], sp["w_up"], sp["w_down"], sp["gate"]]
        in_specs += [P(None, "model"), P(None, "model"), P("model", None),
                     P(None, None)]
    fn = shard_map(local_fn, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=(P(dp_spec, None, None), P()),
                   check_vma=False)
    return fn(*args)


def _moe_apply_global(params: dict, x: jax.Array, cfg: ModelConfig,
                      capacity_factor: float = 1.25
                      ) -> tuple[jax.Array, jax.Array]:
    """Single-device / auto-SPMD reference path (the pre-B1 baseline)."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = _capacity(t, cfg, capacity_factor)
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ params["router"])  # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(gates, k)  # (T, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-transformer load-balancing aux loss.
    me = gates.mean(axis=0)  # (E,)
    ce = jnp.zeros((e,)).at[top_e.reshape(-1)].add(1.0) / (t * k)
    aux = e * jnp.sum(me * ce)

    # ---- dispatch: sort token-expert pairs by expert ----------------------
    pair_e = top_e.reshape(-1)  # (T*k,)
    pair_tok = jnp.repeat(jnp.arange(t), k)
    pair_w = top_w.reshape(-1)
    order = jnp.argsort(pair_e, stable=True)
    pe, ptok, pw = pair_e[order], pair_tok[order], pair_w[order]
    counts = jnp.zeros((e,), jnp.int32).at[pe].add(1)
    offsets = jnp.cumsum(counts) - counts  # start index per expert
    within = jnp.arange(t * k) - offsets[pe]
    keep = within < cap
    dest = jnp.where(keep, pe * cap + within, e * cap)  # overflow -> trash row

    buckets = jnp.zeros((e * cap + 1, d), x.dtype).at[dest].set(xt[ptok])
    expert_in = buckets[:-1].reshape(e, cap, d)

    # ---- per-expert gated FFN (batched over experts) ----------------------
    h_gate = jnp.einsum("ecd,edf->ecf", expert_in, params["w_gate"])
    h_up = jnp.einsum("ecd,edf->ecf", expert_in, params["w_up"])
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(x.dtype) * h_up
    h = named(h, None, None, "d_ff")
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])

    # ---- combine ------------------------------------------------------------
    flat = jnp.concatenate(
        [expert_out.reshape(e * cap, d),
         jnp.zeros((1, d), expert_out.dtype)], axis=0)
    pair_out = flat[dest] * (pw * keep).astype(x.dtype)[:, None]
    y = jnp.zeros((t, d), x.dtype).at[ptok].add(pair_out)

    if "shared" in params:
        sp = params["shared"]
        g = jax.nn.silu((xt @ sp["w_gate"]).astype(jnp.float32)).astype(x.dtype)
        hs = g * (xt @ sp["w_up"])
        shared_out = hs @ sp["w_down"]
        mix = jax.nn.sigmoid((xt.astype(jnp.float32) @ sp["gate"]))
        y = y + shared_out * mix.astype(x.dtype)

    return y.reshape(b, s, d), aux
