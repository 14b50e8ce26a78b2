"""Hands a Qwen2-family configuration and its seeded weights to the program.

The program's ``ModelConfig`` is built from the configuration's published
keys, and the tensors that ``references/qwen2.py`` declares are laid out
as the program's parameter tree.  The only change of value: the program
keeps a norm as an offset from 1 (it computes ``1 + w``), so it is given
``gain - 1``.  Nothing here computes a model; the served path does.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from benchmarks.chip.references.qwen2 import weight_specs
from benchmarks.chip.weights import base_key, global_tensors, stacked_layers
from repro.distributed.sharding import serve_pspec, tree_shardings
from repro.models import build_model
from repro.models.config import ModelConfig


def program_config(cfg: dict) -> ModelConfig:
    moe = bool(cfg.get("num_experts"))
    kw: dict[str, Any] = {}
    if moe:
        fe = cfg["moe_intermediate_size"]
        fs = cfg["shared_expert_intermediate_size"]
        if fs % fe:
            raise ValueError(f"shared expert width {fs} is not a whole "
                             f"number of experts of width {fe}")
        if not cfg["norm_topk_prob"]:
            raise ValueError("the program always renormalises the top-k "
                             "router weights; norm_topk_prob must be true")
        # The published model drops no token at inference.  At this
        # capacity factor every expert's bucket holds every token, so the
        # program's capacity limit never drops one either.
        kw = dict(n_experts=cfg["num_experts"],
                  top_k=cfg["num_experts_per_tok"],
                  n_shared_experts=fs // fe, moe_d_ff=fe, d_ff=fe,
                  moe_cf_eval=cfg["num_experts"] / cfg["num_experts_per_tok"])
    else:
        kw = dict(d_ff=cfg["intermediate_size"])
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings are not laid out here")
    return ModelConfig(
        name=cfg["name"], family="moe" if moe else "dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        vocab_size=cfg["vocab_size"], qkv_bias=True, mlp="swiglu",
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        **kw)


def _offset(gain: jax.Array) -> jax.Array:
    return (gain.astype(jnp.float32) - 1.0).astype(gain.dtype)


def to_program(g: dict, lw: dict, padded_vocab: int) -> dict:
    """Named tensors (layers stacked) -> the program's parameter tree."""
    pad = padded_vocab - g["embed_tokens"].shape[0]
    layers: dict[str, Any] = {
        "ln1": _offset(lw["input_layernorm"]),
        "ln2": _offset(lw["post_attention_layernorm"]),
        "attn": {"wq": lw["q_proj"], "wk": lw["k_proj"], "wv": lw["v_proj"],
                 "wo": lw["o_proj"], "bq": lw["q_bias"], "bk": lw["k_bias"],
                 "bv": lw["v_bias"]},
    }
    if "router" in lw:
        layers["moe"] = {
            "router": lw["router"], "w_gate": lw["experts_gate_proj"],
            "w_up": lw["experts_up_proj"], "w_down": lw["experts_down_proj"],
            "shared": {"w_gate": lw["shared_gate_proj"],
                       "w_up": lw["shared_up_proj"],
                       "w_down": lw["shared_down_proj"],
                       "gate": lw["shared_expert_gate"]}}
    else:
        layers["mlp"] = {"w_gate": lw["gate_proj"], "w_up": lw["up_proj"],
                         "w_down": lw["down_proj"]}
    return {"embed": jnp.pad(g["embed_tokens"], ((0, pad), (0, 0))),
            "head": jnp.pad(g["lm_head"], ((0, 0), (0, pad))),
            "ln_f": _offset(g["norm"]), "layers": layers}


def build(cfg: dict) -> Any:
    """The program's model object for ``cfg``."""
    return build_model(program_config(cfg))


def make_params(cfg: dict, model: Any, lo: Any, hi: Any,
                mesh: Optional[Mesh] = None) -> Any:
    """Every weight, made on the device in one jitted call from the seed's
    words, in the dtype the program serves it in; checked against the
    program's own parameter shapes.  With a ``mesh`` (a tensor-parallel
    pod) each weight is drawn straight into the placement the program
    serves it under (``serve_pspec``), so no device holds the whole tree;
    the values are the same as on one device."""
    gspecs, lspecs = weight_specs(cfg)
    n = cfg["num_hidden_layers"]
    vpad = model.cfg.padded_vocab

    def make(lo, hi):
        key = base_key(lo, hi)
        return to_program(global_tensors(key, gspecs),
                          stacked_layers(key, lspecs, n), vpad)

    want = model.abstract_params()
    got = jax.eval_shape(make, lo, hi)
    if (jax.tree_util.tree_structure(got) != jax.tree_util.tree_structure(want)
            or any(a.shape != b.shape or a.dtype != b.dtype for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want)))):
        raise ValueError("the program's parameter layout has changed: "
                         f"{jax.tree_util.tree_map(lambda a: a.shape, want)}")
    if mesh is None:
        return jax.jit(make)(lo, hi)
    return jax.jit(make, out_shardings=tree_shardings(
        model.param_names(), want, mesh, resolver=serve_pspec))(lo, hi)
