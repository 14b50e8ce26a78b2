"""Block-paged KV cache: allocator, page table, engine, and equivalence.

Tier-1 tests on the tiny deterministic configs from ``conftest`` — this is
the CI smoke for the paged hot path.  Covers the ISSUE-3 edge cases:
block exhaustion under admission pressure, double-free rejection,
free-list reuse after retire, and paged-vs-dense decode equivalence per
model family.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny_config
from repro.core.resources import Alloc
from repro.models import build_model
from repro.serving import (NULL_BLOCK, BlockExhausted, ClusterFrontend,
                           KVPageAllocator, PageTable, ServingEngine,
                           blocks_needed)

FULL = Alloc(sm=1.0, quota_request=0.9, quota_limit=0.9)


def _prompts(spec, rng_seed=0, vocab=64):
    """spec: list of (prompt_len, max_new_tokens)."""
    rng = np.random.default_rng(rng_seed)
    return [(rng.integers(0, vocab, l, dtype=np.int32), n) for l, n in spec]


def _serve(model, params, batching, arrivals, *, max_batch=2, max_len=32,
           block_size=8, n_kv_blocks=None):
    engine = ServingEngine(window=0.1)
    engine.deploy("f", model, params, FULL, n_instances=1,
                  max_batch=max_batch, max_len=max_len, batching=batching,
                  block_size=block_size, n_kv_blocks=n_kv_blocks)
    reqs = [engine.submit("f", p, max_new_tokens=n) for p, n in arrivals]
    done = engine.pump(budget_s=120.0)
    assert done == len(reqs)
    return reqs, engine


def _only_instance(engine):
    return next(iter(engine.instances.values()))


# -- allocator units -------------------------------------------------------


def test_allocator_exhaustion_and_reuse():
    a = KVPageAllocator(n_blocks=5, block_size=8)  # 4 usable + null
    assert a.capacity == 4
    got = a.alloc(4)
    assert NULL_BLOCK not in got and len(set(got)) == 4
    assert not a.can_alloc(1)
    with pytest.raises(BlockExhausted):
        a.alloc(1)
    a.free(got[:2])
    # Freed blocks are recycled (appended, so reused in retire order).
    again = a.alloc(2)
    assert set(again) == set(got[:2])
    assert a.high_watermark == 4
    assert a.stats()["allocs"] == 6 and a.stats()["frees"] == 2


def test_allocator_rejects_double_and_foreign_free():
    a = KVPageAllocator(n_blocks=4, block_size=8)
    got = a.alloc(2)
    a.free(got)
    with pytest.raises(ValueError):
        a.free([got[0]])  # double free
    with pytest.raises(ValueError):
        a.free([NULL_BLOCK])  # the null block is never allocated
    # A rejected free must not have mutated the free list.
    assert a.free_blocks() == a.capacity and a.blocks_in_use == 0


def test_allocator_defrag_stats():
    a = KVPageAllocator(n_blocks=9, block_size=8)
    held = a.alloc(8)
    a.free(held[1::2])  # every other block -> maximally fragmented
    assert a.fragmentation() > 0.5
    a.free(held[0::2])
    assert a.defrag() == 0.0  # fully free list is one contiguous run
    assert a.stats()["defrags"] == 1


def test_page_table_rows_and_release():
    a = KVPageAllocator(n_blocks=8, block_size=4)
    t = PageTable(a)
    t.allocate(1, 9)  # 3 blocks
    t.allocate(2, 4)  # 1 block
    assert blocks_needed(9, 4) == 3 and len(t.blocks(1)) == 3
    row = t.row(1, max_blocks=5)
    assert row[:3] == t.blocks(1) and row[3:] == [NULL_BLOCK, NULL_BLOCK]
    with pytest.raises(ValueError):
        t.allocate(1, 4)  # id already live
    freed = t.release(1)
    assert a.blocks_in_use == 1 and len(freed) == 3
    assert t.release_all() == 1 and a.blocks_in_use == 0


# -- paged vs dense decode equivalence, per family -------------------------


MOE_KW = dict(name="tiny-moe", family="moe", n_experts=4, top_k=2)


@pytest.mark.parametrize("overrides", [{}, MOE_KW],
                         ids=["dense", "moe"])
def test_paged_matches_continuous_tokens(overrides):
    """Same mixed-length arrivals: the paged engine must emit exactly the
    dense slot-pool token streams (logit-path equivalence end to end)."""
    model = build_model(tiny_config(**overrides))
    params = model.init(jax.random.key(0))
    arrivals = _prompts([(4, 3), (12, 6), (7, 2), (20, 5), (5, 4), (16, 6)])
    cont, _ = _serve(model, params, "continuous", arrivals)
    paged, eng = _serve(model, params, "paged", arrivals)
    for rc, rp in zip(cont, paged):
        assert rc.done and rp.done
        assert rc.tokens_out == rp.tokens_out
    inst = _only_instance(eng)
    assert inst.refills > 0, "trace must exercise mid-flight admission"
    assert inst.allocator.blocks_in_use == 0, "drained engine leaked blocks"


def test_paged_decode_logits_match_dense(tiny_model, tiny_params):
    """Raw logits: decode_step_paged == decode_step within tolerance, with
    scrambled physical block order and an idle slot in the batch."""
    max_len, bs = 32, 8
    prompt = np.arange(9, dtype=np.int32) % tiny_model.cfg.vocab_size
    logits0, entry = jax.jit(
        lambda p, t: tiny_model.prefill(p, t, max_len=max_len))(
        tiny_params, jnp.asarray(prompt[None], jnp.int32))

    dense = dict(entry)
    cache = tiny_model.init_paged_cache(9, bs)
    row = jnp.asarray([3, 1, 4, 2], jnp.int32)  # scrambled physical order
    cache = tiny_model.append_paged(cache, entry, row)
    tables = jnp.zeros((2, max_len // bs), jnp.int32).at[0].set(row)
    pos = jnp.asarray([9, 0], jnp.int32)

    tok = jnp.argmax(logits0, -1).astype(jnp.int32)
    for _ in range(4):
        dl, dense = jax.jit(tiny_model.decode_step)(tiny_params, tok, dense)
        pl, cache = jax.jit(tiny_model.decode_step_paged)(
            tiny_params, jnp.asarray([int(tok[0]), 0], jnp.int32),
            cache, tables, pos)
        np.testing.assert_allclose(np.asarray(dl[0]), np.asarray(pl[0]),
                                   rtol=1e-4, atol=1e-4)
        pos = pos + 1
        tok = jnp.argmax(dl, -1).astype(jnp.int32)


def test_append_gather_pages_roundtrip(tiny_model, tiny_params):
    """gather_pages(append_paged(cache, entry, row), row) == entry."""
    prompt = np.arange(8, dtype=np.int32) % tiny_model.cfg.vocab_size
    _, entry = jax.jit(
        lambda p, t: tiny_model.prefill(p, t, max_len=32))(
        tiny_params, jnp.asarray(prompt[None], jnp.int32))
    cache = tiny_model.init_paged_cache(9, 8)
    row = jnp.asarray([5, 2, 7, 1], jnp.int32)
    cache = tiny_model.append_paged(cache, entry, row)
    back = tiny_model.gather_pages(cache, row, entry["pos"])
    for key in ("k", "v", "pos"):
        np.testing.assert_array_equal(
            np.asarray(back[key], np.float32),
            np.asarray(entry[key], np.float32), err_msg=key)


def _xs_ys_round(params, tok, cache, tables, pos, active, cfg):
    """The fused paged round as its layer scan stood before the pools rode
    in the carry: each layer's pages sliced out as ``xs``, written and
    read, and re-stacked as ``ys``."""
    from repro.models import attention, transformer

    keys = (("k", "v", "k_scale", "v_scale")
            if attention.kv_int8_enabled(cfg) else ("k", "v"))
    block = (transformer.block_decode_paged_quant if len(keys) == 4
             else transformer.block_decode_paged)

    def body(x, xs):
        lp, *pages = xs
        x, *pages = block(lp, x, *pages, tables, pos, cfg, active)
        return x, tuple(pages)

    x = transformer.embed_tokens(params, tok[:, None], cfg)
    x, pools = jax.lax.scan(body, x, (params["layers"],
                                      *(cache[k] for k in keys)))
    logits = transformer.lm_head(params, x, cfg)[:, 0]
    return (transformer.greedy_tokens(logits, cfg),
            dict(cache, **dict(zip(keys, pools))), pos + active)


@pytest.mark.parametrize("kv_int8", [False, True], ids=["bf16", "int8"])
def test_carried_pool_round_matches_xs_ys_scan(tiny_model, tiny_params,
                                               monkeypatch, kv_int8):
    """Fused paged rounds with the pools carried through the layer scan
    are bit-identical to the old xs/ys scan: tokens, positions and every
    pool, with two free slots and a prefix block shared by two sequences;
    free slots write nothing, so the null block stays zero."""
    if kv_int8:
        monkeypatch.setenv("REPRO_KV_INT8", "1")
    else:
        monkeypatch.delenv("REPRO_KV_INT8", raising=False)
    bs, n_blocks = 4, 12
    shapes = tiny_model.paged_cache_shapes(n_blocks, bs)
    keys = jax.random.split(jax.random.key(3), len(shapes))
    cache = {}
    for key, (name, sds) in zip(keys, sorted(shapes.items())):
        if sds.dtype == jnp.int8:
            pool = jax.random.randint(key, sds.shape, -127, 128, jnp.int32)
        else:
            pool = jax.random.uniform(key, sds.shape, jnp.float32, 0.01, 1.0)
        cache[name] = pool.astype(sds.dtype).at[:, 0].set(0)  # null block
    # Slots 0 and 1 share prefix block 1 (rows 0-3) and write past it;
    # slots 2 and 4 are free; slot 3 owns its blocks.
    tables = jnp.asarray([[1, 2, 6], [1, 3, 7], [0, 0, 0], [4, 5, 8],
                          [0, 0, 0]], jnp.int32)
    pos = jnp.asarray([6, 5, 0, 7, 3], jnp.int32)
    active = jnp.asarray([1, 1, 0, 1, 0], jnp.int32)
    tok = jnp.asarray([5, 9, 0, 17, 0], jnp.int32)
    new = jax.jit(tiny_model.decode_step_paged_tokens)
    old = jax.jit(lambda *a: _xs_ys_round(*a, tiny_model.cfg))
    state_new = state_old = (tok, cache, pos)
    for _ in range(5):
        state_new = new(tiny_params, state_new[0], state_new[1], tables,
                        state_new[2], active)
        state_old = old(tiny_params, state_old[0], state_old[1], tables,
                        state_old[2], active)
        np.testing.assert_array_equal(np.asarray(state_new[0]),
                                      np.asarray(state_old[0]))
        np.testing.assert_array_equal(np.asarray(state_new[2]),
                                      np.asarray(state_old[2]))
    assert np.asarray(state_new[2]).tolist() == [11, 10, 0, 12, 3]
    assert set(state_new[1]) == set(shapes)
    for name in shapes:
        got = np.asarray(state_new[1][name])
        np.testing.assert_array_equal(got, np.asarray(state_old[1][name]),
                                      err_msg=name)
        assert not got[:, 0].any(), f"{name}: the null block was written"
        np.testing.assert_array_equal(got[:, 1], np.asarray(cache[name][:, 1]),
                                      err_msg=f"{name}: shared block written")
        assert (got != np.asarray(cache[name])).any(), f"{name}: unwritten"


# -- engine: block budgeting, release, reuse -------------------------------


def test_block_exhaustion_under_admission_pressure(tiny_model, tiny_params):
    """A pool too small for two concurrent requests must serialize them —
    the queue waits for blocks, nothing is dropped, nothing leaks."""
    # Each request needs ceil((8 + 4 - 1) / 8) = 2 blocks; 3 usable blocks
    # admit exactly one at a time even though 2 decode slots are free.
    arrivals = _prompts([(8, 4)] * 4)
    reqs, eng = _serve(tiny_model, tiny_params, "paged", arrivals,
                       max_batch=2, n_kv_blocks=4)
    inst = _only_instance(eng)
    assert all(r.done and len(r.tokens_out) == 4 for r in reqs)
    assert inst.allocator.high_watermark <= 3
    assert inst.allocator.blocks_in_use == 0
    # Free-list reuse: 4 requests x 2 blocks through a 3-block pool is
    # only possible if freed blocks were recycled.
    assert inst.allocator.stats()["allocs"] == 8
    assert inst.allocator.stats()["frees"] == 8


def test_blocks_released_on_retire_drain(tiny_model, tiny_params):
    """Graceful scale-down: draining slots release their blocks into the
    free list as they finish; the closed instance leaves zero in use."""
    engine = ServingEngine(window=0.1)
    ids = engine.deploy("f", tiny_model, tiny_params, FULL, n_instances=1,
                        max_batch=2, max_len=32, batching="paged",
                        block_size=8)
    arrivals = _prompts([(8, 6), (8, 6), (8, 3)])
    reqs = [engine.submit("f", p, max_new_tokens=n) for p, n in arrivals]
    # Admit into slots, then retire mid-flight: queued strays come back,
    # occupied slots keep decoding under the token scheduler.  Step a
    # fixed count (not a wall-clock pump) so slots are still mid-decode
    # at retire even with warm shared executor caches.
    inst = engine.instances[ids[0]]
    inst.run_step()
    inst.run_step()
    alloc_ref = inst.allocator
    assert alloc_ref.blocks_in_use > 0, "test needs live paged slots"
    strays = engine.retire(ids[0], strip_queue=True)
    engine.pump(budget_s=120.0)
    assert ids[0] not in engine.instances, "drained instance must close"
    assert alloc_ref.blocks_in_use == 0, "retire leaked KV blocks"
    admitted = [r for r in reqs if r not in strays]
    assert all(r.done for r in admitted)
    assert alloc_ref.free_blocks() == alloc_ref.capacity


def test_paged_kv_bytes_strictly_below_dense_through_frontend(tiny_model,
                                                              tiny_params):
    """Acceptance: a mixed-length workload through ``ClusterFrontend`` with
    ``batching="paged"`` keeps per-step physical KV bytes-in-use strictly
    below the dense slot-pool reservation, with identical tokens out."""
    arrivals = _prompts([(4, 3), (14, 6), (6, 2), (22, 5), (5, 4),
                         (11, 3), (8, 6), (17, 2)], rng_seed=3)

    def run(batching):
        frontend = ClusterFrontend(n_nodes=2, window=0.1)
        frontend.deploy("lm", tiny_model, tiny_params,
                        Alloc(sm=0.45, quota_request=0.45, quota_limit=0.6),
                        n_instances=2, max_batch=4, max_len=32,
                        batching=batching, block_size=8)
        reqs = [frontend.submit("lm", p, max_new_tokens=n)
                for p, n in arrivals]
        done = frontend.pump(budget_s=120.0)
        assert done == len(reqs) and all(r.done for r in reqs)
        insts = [i for e in frontend.engines for i in e.instances.values()]
        return reqs, frontend, insts

    dense_reqs, dense_fe, _ = run("continuous")
    paged_reqs, paged_fe, insts = run("paged")
    # Same tokens out of both data planes (requests route identically:
    # same arrival order, same JSQ state evolution).
    assert ([r.tokens_out for r in paged_reqs]
            == [r.tokens_out for r in dense_reqs])
    # Per-step peak of every paged instance stays strictly below what the
    # dense pool reserves for the same slot capacity.
    for inst in insts:
        assert inst.kv_bytes_peak > 0
        assert inst.kv_bytes_peak < inst.dense_kv_reserved()
    assert paged_fe.kv_bytes_in_use() == 0  # all blocks back after drain
    assert paged_fe.dense_kv_reserved() == dense_fe.dense_kv_reserved()


def test_paged_admission_charges_block_budget_not_max_len():
    """Memory admission sees real block bytes: a paged deployment with a
    small block budget fits where the dense slot pool does not."""
    model = build_model(tiny_config())
    params = model.init(jax.random.key(0))
    alloc = Alloc(sm=0.2, quota_request=0.2, quota_limit=0.3)
    # Budget chosen so framework + dense KV overflows but framework +
    # 5-block paged KV fits (weights + server overhead dominate the rest).
    dense_kv = model.dense_kv_bytes(4, 64)
    paged_kv = model.kv_cache_bytes(batching="paged", max_batch=4,
                                    max_len=64, block_size=16, n_kv_blocks=5)
    assert paged_kv < dense_kv
    from repro.core.model_sharing import (SERVER_CONTEXT_OVERHEAD,
                                          pytree_nbytes)
    base = pytree_nbytes(params) + SERVER_CONTEXT_OVERHEAD
    fw = 1024
    budget = base + fw + paged_kv + (dense_kv - paged_kv) // 2
    fe_dense = ClusterFrontend(n_nodes=1, mem_bytes=budget)
    assert fe_dense.place_instance("f", model, params, alloc,
                                   framework_bytes=fw) is None
    fe_paged = ClusterFrontend(n_nodes=1, mem_bytes=budget)
    assert fe_paged.place_instance("f", model, params, alloc,
                                   batching="paged", n_kv_blocks=5,
                                   framework_bytes=fw) is not None


def test_profiled_kv_blocks_drive_paged_pool(tiny_model, tiny_params):
    """LiveBackend.place sizes the paged pool from the profile table's
    ``kv_blocks`` when the spec gives no explicit budget."""
    from repro.control.backend import LiveBackend
    from repro.control.spec import FunctionSpec
    from repro.core.profiler import paged_kv_capacity
    from repro.core.scaling import ProfilePoint

    block_bytes = tiny_model.kv_block_bytes(8)
    budget = 7 * block_bytes + block_bytes // 2
    kv_blocks = paged_kv_capacity(budget, block_bytes)
    assert kv_blocks == 7  # TOTAL pool size incl. the null block
    assert paged_kv_capacity(block_bytes, block_bytes) == 0  # null-only

    spec = FunctionSpec(
        name="f",
        profile=(ProfilePoint(sm=0.3, quota=0.3, throughput=1.0,
                              kv_blocks=kv_blocks),),
        batching="paged", block_size=8, max_len=32,
        model_factory=lambda: (tiny_model, tiny_params))
    frontend = ClusterFrontend(n_nodes=1)
    backend = LiveBackend(frontend)
    backend.register(spec)
    assert backend.place(spec, spec.profile[0]) is not None
    inst = next(iter(frontend.engines[0].instances.values()))
    assert inst.allocator.n_blocks == kv_blocks
    assert inst.allocator.capacity == kv_blocks - 1


def test_frontend_rejects_mixed_data_plane_configs(tiny_model, tiny_params):
    """One MemoryModel per function: a second placement with a different
    KV footprint must be rejected, not silently mis-accounted."""
    frontend = ClusterFrontend(n_nodes=2)
    alloc = Alloc(sm=0.2, quota_request=0.2, quota_limit=0.3)
    assert frontend.place_instance("f", tiny_model, tiny_params,
                                   alloc) is not None
    with pytest.raises(ValueError, match="different per-instance"):
        frontend.place_instance("f", tiny_model, tiny_params, alloc,
                                batching="paged", n_kv_blocks=4)
    # Same config again is fine.
    assert frontend.place_instance("f", tiny_model, tiny_params,
                                   alloc) is not None


def test_free_with_duplicate_ids_is_all_or_nothing():
    a = KVPageAllocator(n_blocks=6, block_size=8)
    got = a.alloc(3)
    with pytest.raises(ValueError):
        a.free([got[0], got[0]])  # duplicate WITHIN one free call
    # Nothing was lost: the rejected free left all three allocated.
    assert a.blocks_in_use == 3
    a.free(got)
    assert a.free_blocks() == a.capacity


def test_default_paged_pool_never_charges_more_than_dense(tiny_model):
    """The documented default (n_kv_blocks=None) must keep the paged
    admission charge at or below the dense slot-pool reservation."""
    for max_batch, max_len, bs in [(4, 64, 16), (2, 32, 8), (1, 32, 16)]:
        paged = tiny_model.kv_cache_bytes(batching="paged",
                                          max_batch=max_batch,
                                          max_len=max_len, block_size=bs)
        dense = tiny_model.dense_kv_bytes(max_batch, max_len)
        assert paged <= dense, (max_batch, max_len, bs)
    # Documented exception: a dense pool of ONE block still needs the null
    # page, so the 2-block minimum charges one extra block there.
    from repro.models.model import default_kv_blocks
    assert default_kv_blocks(1, 16, 16) == 2


def test_oversized_request_rejected_at_submit(tiny_model, tiny_params):
    """A request that cannot fit max_len is rejected up front instead of
    crashing the decode pump mid-admission (and leaking blocks)."""
    engine = ServingEngine(window=0.1)
    engine.deploy("f", tiny_model, tiny_params, FULL, max_batch=2,
                  max_len=16, batching="paged", block_size=8)
    ok = engine.submit("f", np.arange(8, dtype=np.int32), max_new_tokens=4)
    with pytest.raises(ValueError, match="KV rows"):
        engine.submit("f", np.arange(12, dtype=np.int32), max_new_tokens=8)
    # Service continues for well-formed requests; nothing leaked.
    assert engine.pump(budget_s=120.0) == 1 and ok.done
    inst = _only_instance(engine)
    assert inst.allocator.blocks_in_use == 0


def test_redeploy_after_full_drain_with_new_config(tiny_model, tiny_params):
    """Evicting a function's last replica clears its MemoryModel, so a
    redeploy may switch data-plane configs (continuous -> paged)."""
    frontend = ClusterFrontend(n_nodes=1, window=0.1)
    alloc = Alloc(sm=0.3, quota_request=0.3, quota_limit=0.4)
    [handle] = frontend.deploy("f", tiny_model, tiny_params, alloc,
                               batching="continuous")
    frontend.evict(handle)
    frontend.pump(budget_s=10.0)
    assert not frontend.placements
    # Different footprint (paged, tiny block budget) must now be accepted.
    assert frontend.place_instance("f", tiny_model, tiny_params, alloc,
                                   batching="paged",
                                   n_kv_blocks=4) is not None


def test_request_exceeding_pool_capacity_rejected_not_livelocked(
        tiny_model, tiny_params):
    """rows <= max_len but blocks > pool capacity (max_batch=1 default
    pool) must be rejected at submit, not spin _admit forever."""
    engine = ServingEngine(window=0.1)
    engine.deploy("f", tiny_model, tiny_params, FULL, max_batch=1,
                  max_len=32, batching="paged", block_size=8)
    inst = _only_instance(engine)
    assert inst.allocator.capacity == 3  # 4 total - null page
    with pytest.raises(ValueError, match="pool capacity"):
        engine.submit("f", np.arange(26, dtype=np.int32), max_new_tokens=7)
    ok = engine.submit("f", np.arange(20, dtype=np.int32), max_new_tokens=5)
    assert engine.pump(budget_s=120.0) == 1 and ok.done


def test_invalid_block_size_raises_value_error(tiny_model, tiny_params):
    from repro.control.spec import FunctionSpec
    from repro.core.scaling import ProfilePoint

    with pytest.raises(ValueError, match="block_size"):
        FunctionSpec(name="f",
                     profile=(ProfilePoint(sm=0.3, quota=0.3,
                                           throughput=1.0),),
                     batching="paged", block_size=0)
    engine = ServingEngine(window=0.1)
    with pytest.raises(ValueError, match="block_size"):
        engine.deploy("f", tiny_model, tiny_params, FULL,
                      batching="paged", block_size=0)
    # Non-paged specs stay exempt from block-size coupling.
    FunctionSpec(name="f",
                 profile=(ProfilePoint(sm=0.3, quota=0.3, throughput=1.0),),
                 max_len=24)


def test_paged_evict_reroute_across_nodes(tiny_model, tiny_params):
    """Evicting a paged instance re-routes its queued requests to another
    node whose local req-id space overlaps — sequences are keyed by slot,
    so the drain + re-route must complete without collisions or leaks."""
    frontend = ClusterFrontend(n_nodes=2, window=0.1)
    alloc = Alloc(sm=0.45, quota_request=0.45, quota_limit=0.6)
    h0, h1 = frontend.deploy("f", tiny_model, tiny_params, alloc,
                             n_instances=2, max_batch=2, max_len=32,
                             batching="paged", block_size=8)
    reqs = [frontend.submit("f", p, max_new_tokens=n)
            for p, n in _prompts([(8, 6)] * 6, rng_seed=9)]
    # Fixed step counts (not a wall-clock pump) so each node has slots
    # admitted AND requests still queued at evict time, regardless of
    # how warm the shared executor caches are.
    insts = [i for e in frontend.engines for i in e.instances.values()]
    assert len(insts) == 2
    for inst in insts:
        inst.run_step()
        inst.run_step()
        assert inst.n_active() > 0
    frontend.evict(h0)  # queued strays re-route to the other node
    done = frontend.pump(budget_s=120.0)
    assert done == len(reqs) and all(r.done for r in reqs)
    assert frontend.kv_bytes_in_use() == 0


def test_spec_rejects_undersized_kv_pool():
    from repro.control.spec import FunctionSpec
    from repro.core.scaling import ProfilePoint

    with pytest.raises(ValueError, match="n_kv_blocks"):
        FunctionSpec(name="f",
                     profile=(ProfilePoint(sm=0.3, quota=0.3,
                                           throughput=1.0),),
                     batching="paged", n_kv_blocks=1)
