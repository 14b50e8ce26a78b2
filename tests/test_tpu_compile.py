"""Compiles of the served path for a described v5e chip, at Qwen2-7B
widths cut to ``chip_smoke.py``'s depth.

Nothing runs: the TPU compiler, installed here, compiles for a chip that
is described and not attached, and refuses what the chip would refuse
(a block it cannot tile, a program larger than the chip's memory).  The
topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the worker given this file loads
the TPU library.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention
from repro.models import build_model

BLOCK = 16
BATCH = 16          # decode slots
N_BLOCKS = 2048     # a 1 GB KV pool at these widths
PROMPT = 512        # prefill bucket


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip lands in the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def model(smoke):
    return build_model(smoke.qwen2_7b_cut())


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _compile_paged_round(one_chip, model, smoke):
    m = smoke.SmokeConfig(model=model.cfg).max_len // BLOCK
    step = jax.jit(lambda *a: model.decode_step_paged_tokens(*a),
                   donate_argnums=(1, 2, 4))
    return step.lower(
        _on(one_chip, model.abstract_params()), _i32(one_chip, BATCH),
        _on(one_chip, model.paged_cache_shapes(N_BLOCKS, BLOCK)),
        _i32(one_chip, BATCH, m), _i32(one_chip, BATCH),
        _i32(one_chip, BATCH)).compile()


# `%name = bf16[16,2048,16,4,128]{...} opcode(` — array results only.
_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = \w+\[([\d,]*)\]\S* ([\w-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def _moves_of_shape(hlo: str, shapes: set) -> list:
    """Instructions of ``hlo`` (fused ones included) that copy, slice or
    update-slice a result of one of ``shapes``, unit dims ignored."""
    hits = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, dims, opcode = m.groups()
        moves = opcode in _MOVES or (
            opcode == "fusion" and any(w in name for w in _MOVES))
        dims = tuple(int(d) for d in dims.split(",") if d and d != "1")
        if moves and dims in shapes:
            hits.append(line.strip())
    return hits


def test_fused_paged_decode_step_compiles(one_chip, model, smoke):
    """The engine's fused paged round (``decode_paged_tok``): weights and
    a 2048-block pool fit the chip."""
    mem = _compile_paged_round(one_chip, model, smoke).memory_analysis()
    assert mem.argument_size_in_bytes > 9e9  # 16 layers of bf16 weights


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_fused_paged_round_updates_the_pool_in_place(one_chip, model, smoke,
                                                     monkeypatch, kv_int8):
    """The layer scan carries the stacked pools: no step copies a K/V pool
    or slices a layer's pool out and re-stacks it, so the step's
    temporaries stay below one layer's K pages.  (int8's per-row scale
    pools, 1/128 of the codes, may be staged through on-chip memory.)"""
    if kv_int8:
        monkeypatch.setenv("REPRO_KV_INT8", "1")
    else:
        monkeypatch.delenv("REPRO_KV_INT8", raising=False)
    pools = model.paged_cache_shapes(N_BLOCKS, BLOCK)
    compiled = _compile_paged_round(one_chip, model, smoke)
    k = pools["k"]
    layer_k_bytes = math.prod(k.shape[1:]) * k.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
    shapes = {tuple(d for d in shape if d != 1)
              for p in (pools["k"], pools["v"])
              for shape in (p.shape, p.shape[1:])}
    assert _moves_of_shape(compiled.as_text(), shapes) == []


def test_bucketed_prefill_compiles(one_chip, model, smoke):
    """The engine's length-masked prefill (``prefill_len``) at the smoke
    run's prompt bucket and ``max_len``."""
    max_len = smoke.SmokeConfig(model=model.cfg).max_len
    prefill = jax.jit(lambda p, t, n: model.prefill(p, t, max_len=max_len,
                                                    length=n))
    compiled = prefill.lower(_on(one_chip, model.abstract_params()),
                             _i32(one_chip, 1, PROMPT),
                             _i32(one_chip)).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


@pytest.mark.parametrize("paged", [True, False, "stacked"],
                         ids=["paged", "dense", "stacked"])
def test_pallas_decode_kernel_compiles(one_chip, model, smoke, paged):
    """Both decode kernels lower to Mosaic (``tpu_custom_call``) for v5e,
    the paged one also reading a layer of stacked (L, N, ...) pools."""
    cfg = model.cfg
    max_len = smoke.SmokeConfig(model=cfg).max_len
    bf16 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16,
                                               sharding=one_chip)
    q = bf16(BATCH, 1, cfg.n_heads, cfg.dh)
    cache_len = _i32(one_chip, BATCH)
    if paged:
        lead = (cfg.n_layers,) if paged == "stacked" else ()
        layer = (_i32(one_chip),) if lead else ()
        pages = bf16(*lead, N_BLOCKS, BLOCK, cfg.n_kv_heads, cfg.dh)
        fn = jax.jit(lambda *a: decode_attention
                     .paged_decode_attention_pallas(*a, interpret=False))
        lowered = fn.lower(q, pages, pages,
                           _i32(one_chip, BATCH, max_len // BLOCK), cache_len,
                           *layer)
    else:
        cache = bf16(BATCH, 2 * PROMPT, cfg.n_kv_heads, cfg.dh)
        fn = jax.jit(lambda *a: decode_attention.decode_attention_pallas(
            *a, interpret=False))
        lowered = fn.lower(q, cache, cache, cache_len)
    assert "tpu_custom_call" in lowered.compile().as_text()
