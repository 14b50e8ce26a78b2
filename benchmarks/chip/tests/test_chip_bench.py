"""CPU tests of the chip benchmark's harness, at a tiny size.

    python -m pytest benchmarks/chip/tests -q

The harness's chip check is skipped (``platform="cpu"``); everything else
of a run is driven: weights from the seed, the served path, the window,
the metrics and the comparison with the reference.
"""

from __future__ import annotations

import copy
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check, flops, peaks, run, stats, traffic
from benchmarks.chip import trace as devtrace

DATA = Path(__file__).parent / "data"

TINY = {
    "name": "tiny-qwen2", "model_type": "qwen2", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "tie_word_embeddings": False,
    "assumed": {"init": {"embed_std": 0.02, "norm_gain_std": 0.2,
                         "qkv_bias_std": 0.5}},
    "deployment": {"instances": 2, "slots_per_instance": 4,
                   "alloc": {"sm": 0.5, "quota_request": 0.5,
                             "quota_limit": 1.0},
                   "block_size": 16, "prefix_sharing": True},
    # Sound tiny runs read widest gaps of 0.01-0.03 on the CPU, and the
    # float8 control 0.1-0.16 (seeds 11-13).
    "correct": {"max_logit_gap": 0.05, "min_tokens_compared": 10},
}
TINY_MOE = dict(
    TINY, name="tiny-moe", model_type="qwen2_moe", num_key_value_heads=4,
    num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    shared_expert_intermediate_size=64, norm_topk_prob=True,
    # The mean gap, as for the MoE cell: a tiny sound run reads 6e-4.
    correct={"mean_logit_gap": 0.01, "min_tokens_compared": 10},
    deployment=dict(TINY["deployment"], instances=1, slots_per_instance=8,
                    alloc={"sm": 1.0, "quota_request": 1.0,
                           "quota_limit": 1.0}))

# One instance as a tensor-parallel pod over the four host devices, with a
# KV head per device as Qwen2-7B's 4 over 4 chips.  (Where the KV heads do
# not divide over the devices, the program's pool comes out of a step
# sharded otherwise than it went in, and the window compiles again.)  Its
# pool is 60 blocks, where 8 slots at the longest request would take 80.
TINY_POD = dict(
    TINY, name="tiny-qwen2-tp4", num_key_value_heads=4,
    deployment=dict(TINY["deployment"], instances=1, shards=4,
                    slots_per_instance=8, kv_blocks=60,
                    alloc={"sm": 1.0, "quota_request": 1.0,
                           "quota_limit": 1.0}))

BENCH = run.load_benchmark()
CHAT_CELL = {"name": "qwen2-7b-16l.chat-prefix", "chips": 1}
AGENT_CELL = {"name": "qwen2-moe-a2.7b-8l.agent-decode", "chips": 1}
POD_CELL = {"name": "qwen2-7b-tp4.chat-prefix", "chips": 4}


def tiny_chat() -> dict:
    mix = run.load_json("traffic", "chat-prefix")
    mix.update(shared_prefix={"count": 3, "tokens": 48, "zipf_s": 1.1},
               prompt_cap=128, rate_per_s=20.0,
               sessions={"mean_turns": 3, "turn_gap_s": 0.5},
               new_tokens={"median": 16, "sigma": 0.8, "min": 8, "max": 40},
               reply_tokens={"median": 12, "sigma": 0.6, "min": 4,
                             "max": 24})
    return mix


def tiny_agent() -> dict:
    mix = run.load_json("traffic", "agent-decode")
    mix.update(clients=4, start_spread_s=0.2,
               shared_prefix={"count": 1, "tokens": 32},
               prompt_cap=64,
               new_tokens={"median": 16, "sigma": 0.6, "min": 8, "max": 32},
               reply_tokens={"median": 24, "sigma": 0.5, "min": 12,
                             "max": 48})
    return mix


def run_tiny(cfg: dict, mix: dict, cell: dict, seed: int = 2**33 + 5
             ) -> dict:
    return run.run_cell(cell, cfg, mix,
                        run.cell_metrics(BENCH, cell, trace=False),
                        seed=seed, seconds=2.0, trace=False,
                        platform="cpu")


# -- the generator -----------------------------------------------------------


@pytest.mark.parametrize("mix", [tiny_chat(), tiny_agent()],
                         ids=["open-sessions", "closed"])
def test_generator_is_a_function_of_the_seed(mix):
    a = traffic.generate(mix, 256, 2**33 + 1, 5.0)
    b = traffic.generate(mix, 256, 2**33 + 1, 5.0)
    c = traffic.generate(mix, 256, 7, 5.0)

    def flat(t):
        return t.arrivals + [r for cl in t.clients for r in cl]

    for x, y in zip(flat(a), flat(b)):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.due_s) == (y.max_new_tokens, y.due_s)
    assert len(flat(a)) == len(flat(c))
    # Another seed sends other text, in another order...
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(flat(a), flat(c)))
    # ...but the same replies and gaps: the same work.
    assert (sorted(r.max_new_tokens for r in flat(a))
            == sorted(r.max_new_tokens for r in flat(c)))
    if mix["loop"] == "open":
        assert a.arrivals[-1].due_s == pytest.approx(c.arrivals[-1].due_s)
        assert a.arrivals[-1].due_s < 5.0


def test_chat_prompts_hold_prefix_history_and_cap():
    mix = tiny_chat()
    t = traffic.generate(mix, 256, 3, 5.0)
    prefixes = {r.prompt[:48].tobytes() for r in t.arrivals}
    assert 1 < len(prefixes) <= 3
    assert all(len(r.prompt) <= mix["prompt_cap"] for r in t.arrivals)
    assert max(len(r.prompt) for r in t.arrivals) > 48 + 40  # history
    assert all(traffic.min_prompt(mix) <= len(r.prompt)
               <= traffic.max_prompt(mix) for r in t.arrivals)


# -- the arithmetic ------------------------------------------------------------


def test_percentile_is_linear_between_ranks():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    assert stats.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_end_to_end_counts_every_request_a_stall_included():
    def sent(due, times):
        s = run.Sent(req=None, due=due, sent=due, prompt_len=10)
        s.times = list(times)
        return s

    # Window [0, 10): two served requests and one that never got a token.
    reqs = [sent(0.0, [0.1, 0.2, 0.4]), sent(1.0, [1.5, 1.6]),
            sent(2.0, [])]
    rec = run.RunRecord(cfg={}, seconds=10.0, sent=reqs,
                        end=10.0, lags=[], telemetry={}, block_size=16)
    m = run.end_to_end(rec)
    ttft = [100.0, 500.0, 8000.0]  # the stalled one waited to the close
    assert m["ttft_p50_ms"] == pytest.approx(stats.percentile(ttft, 50))
    assert m["ttft_p95_ms"] == pytest.approx(stats.percentile(ttft, 95))
    assert m["ttft_p95_ms"] > 7000.0
    gaps = [100.0, 200.0, 100.0]
    assert m["itl_p95_ms"] == pytest.approx(stats.percentile(gaps, 95))
    assert m["out_tokens_per_s"] == pytest.approx(5 / 10.0)
    # A token after the close does not count.
    reqs[1].times.append(10.5)
    assert run.end_to_end(rec)["out_tokens_per_s"] == pytest.approx(0.5)


def test_flops_match_a_hand_count():
    # d=64, 4 heads of 16, 2 kv heads, ffn 128, 2 layers, vocab 256.
    attn = 64 * 64 + 2 * 64 * 32 + 64 * 64        # q, k, v, o
    mlp = 3 * 64 * 128
    assert flops.matmul_params_per_layer(TINY) == attn + mlp
    per_layer_token = 2 * (attn + mlp)
    head = 2 * 64 * 256
    # A 3-token prompt attends 1 + 2 + 3 keys: 4 * H * dh each.
    want = 2 * (3 * per_layer_token + 4 * 64 * 6) + head
    assert flops.prompt_flops(TINY, 3) == want
    assert flops.decode_flops(TINY, 4) == (
        2 * (per_layer_token + 4 * 64 * 4) + head)
    # MoE: router, top-2 of 8 experts of 32, one shared expert of 64 + gate.
    moe = 64 * 8 + 2 * 3 * 64 * 32 + 3 * 64 * 64 + 64
    assert flops.matmul_params_per_layer(TINY_MOE) == 64 * 64 * 4 + moe
    # Served: the prompt, then tokens 2..n; prefix hits come off.
    one = flops.prompt_flops(TINY, 3) + flops.decode_flops(TINY, 4)
    assert flops.served_flops(TINY, [(3, 2), (5, 0)], 0) == one
    assert flops.served_flops(TINY, [(3, 2)], 1) == (
        one - flops.token_flops(TINY, 3))


def test_peaks_refuse_an_unknown_chip():
    assert peaks.peak("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peak("TPU v99")


def test_trace_reduction_on_a_recorded_chip_trace():
    s = devtrace.summarize(str(DATA / "small_trace.xplane.pb"))
    assert 0 < s["busy_s"] < s["window_s"]
    assert s["device_ops"] and all(t > 0 for _, t in s["device_ops"])
    assert sum(t for _, t in s["device_ops"]) <= s["window_s"]
    # The sleeps are the longest idle gaps, and are blamed on them.
    assert s["idle_gaps"][0][0] == "bench.wait"
    assert s["idle_gaps"][0][1] == pytest.approx(0.01, rel=0.5)


def test_collective_time_is_the_union_of_collective_ops():
    ops = [
        (0, 10, "%fusion.1 = bf16[8]{0} fusion(%p)"),
        (5, 20, "%all-gather-start.3 = (bf16[8]{0}) all-gather-start(%a)"),
        (15, 30, "%all-gather-done.3 = bf16[32]{0} all-gather-done(%s)"),
        (40, 50, "%all-reduce.7 = f32[8]{0} all-reduce(%b)"),
        (45, 48, "%reduce-scatter.1 = f32[2]{0} reduce-scatter(%c)"),
        (60, 70, "%collective-permute-done = f32[8]{0} "
                 "collective-permute-done(%d)"),
        (80, 90, "%all-to-all.2 = f32[8]{0} all-to-all(%e)"),
        (95, 99, "%copy-start = f32[8]{0} copy-start(%f)")]
    # [5, 30) from the overlapping halves, [40, 50), [60, 70), [80, 90).
    assert devtrace.collective_ns(ops, 0, 100) == 25 + 10 + 10 + 10
    assert devtrace.collective_ns(ops, 10, 85) == 20 + 10 + 10 + 5
    assert devtrace.collective_ns(ops[:1] + ops[-1:], 0, 100) == 0


def test_mfu_divides_by_every_chip_of_the_trace():
    s = run.Sent(req=None, due=0.0, sent=0.0, prompt_len=40)
    s.times = [0.5, 0.6, 0.7]
    rec = run.RunRecord(cfg=TINY, seconds=10.0, sent=[s], end=10.0,
                        lags=[], telemetry={"shared_hits": 0},
                        block_size=16, peak=peaks.peak("TPU v5 lite"),
                        trace={"busy_s": 2.0, "window_s": 10.0,
                               "devices": 1})
    useful = flops.served_flops(TINY, [(40, 3)], 0)
    one = flops.busy_mfu(rec)
    assert one == pytest.approx(100.0 * useful / (2.0 * 197e12))
    rec.trace["devices"] = 4
    assert flops.busy_mfu(rec) == pytest.approx(one / 4)
    rec.trace["collective_s"] = 0.5
    read = run.load_module("layer_metrics", "collective_share").read
    assert read(rec) == pytest.approx(25.0)
    assert run.load_module("layer_metrics", "mfu.tp4").read(rec) == \
        pytest.approx(one / 4)


def test_mfu_of_a_traced_part_counts_the_work_done_in_it():
    """Where the trace covers only the window's end, the useful FLOPs are
    those of the tokens delivered after it began, a prompt only where its
    first token came then, and the prefix hits counted then."""
    early, late = (run.Sent(req=None, due=0.0, sent=0.0, prompt_len=40),
                   run.Sent(req=None, due=0.0, sent=0.0, prompt_len=24))
    early.times, late.times = [0.5, 0.6, 0.7], [0.65, 0.8]
    rec = run.RunRecord(cfg=TINY, seconds=1.0, sent=[early, late], end=1.0,
                        lags=[], telemetry={"shared_hits": 3},
                        block_size=16, peak=peaks.peak("TPU v5 lite"),
                        trace={"busy_s": 0.25, "window_s": 0.45,
                               "devices": 4},
                        traced_from=0.55,
                        traced_telemetry={"shared_hits": 1})
    useful = (flops.decode_flops(TINY, 41) + flops.decode_flops(TINY, 42)
              + flops.prompt_flops(TINY, 24) + flops.decode_flops(TINY, 25)
              - 16 * flops.token_flops(TINY, 24))
    assert flops.part_flops(TINY, [(40, 1, 3), (24, 0, 2)], 16) == useful
    assert flops.busy_mfu(rec) == pytest.approx(
        100.0 * useful / (0.25 * 4 * 197e12))
    # A trace that began before every token reads as the whole window.
    rec.traced_from, rec.traced_telemetry = 0.0, rec.telemetry
    whole = flops.busy_mfu(rec)
    rec.traced_from = None
    assert flops.busy_mfu(rec) == whole


def test_the_trace_covers_the_part_a_configuration_asks_for():
    cfg = {"trace": {"last_seconds": 10}}
    assert run.traced_seconds(cfg, 50.0) == 10
    assert run.traced_seconds(cfg, 8.0) is None
    assert run.traced_seconds(TINY, 50.0) is None
    assert run.traced_seconds(run.load_json("configs", "qwen2-7b-tp4"),
                              50.0) == 10
    for name in ("qwen2-7b-16l", "qwen2-moe-a2.7b-8l"):
        assert run.traced_seconds(run.load_json("configs", name),
                                  50.0) is None


@pytest.mark.parametrize("traced_s", [None, 0.15], ids=["whole", "last"])
def test_the_window_span_opens_where_the_trace_begins(traced_s):
    """The window span covers the whole window, or opens as the traced part
    begins, right after the trace is started, and closes at the end."""
    import contextlib
    import time

    class Idle:
        def has_work(self):
            return False

    events = []

    @contextlib.contextmanager
    def span(name):
        events.append(("open", name, time.perf_counter()))
        yield
        events.append(("close", name, time.perf_counter()))

    def start_trace():
        events.append(("start", None, time.perf_counter()))

    plan = traffic.Traffic(loop="open", arrivals=[], clients=[])
    t0 = time.perf_counter()
    _, end, _ = run.serve_window(Idle(), plan, 0.4, span, traced_s,
                                 start_trace)
    assert end - t0 == pytest.approx(0.4, abs=0.05)
    window = [e for e in events if e[1] == devtrace.WINDOW]
    assert [e[0] for e in window] == ["open", "close"]
    assert window[1][2] >= end
    starts = [e for e in events if e[0] == "start"]
    if traced_s is None:
        assert events[0] == window[0] and not starts
    else:
        assert len(starts) == 1
        assert end - traced_s <= starts[0][2] <= window[0][2]
        assert window[0][2] < end - traced_s + 0.05


def test_the_pod_mix_is_chat_prefix_at_its_own_rate():
    chat = run.load_json("traffic", "chat-prefix")
    pod = run.load_json("traffic", "chat-prefix-tp4")
    for mix in (chat, pod):
        del mix["rate_per_s"], mix["why"]["rate"]
    assert pod == chat
    cfg = run.load_json("configs", "qwen2-7b-tp4")
    assert run.shards(cfg) == 4 and cfg["num_hidden_layers"] == 28


def test_merge_is_the_union_of_intervals():
    assert devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                               (5, 8)]


# -- pieces found by name --------------------------------------------------------


def test_every_piece_is_found_by_its_name():
    for cell in BENCH["workloads"]:
        cfg = run.load_json("configs", cell["config"])
        assert cfg["name"] == cell["config"]
        run.load_json("traffic", cell["traffic"])
        run.adapter(cfg)
        check.reference(cfg)
        for trace in (False, True):
            for m in run.cell_metrics(BENCH, cell, trace):
                if trace:
                    assert callable(run.load_module("layer_metrics",
                                                    m["name"]).read)
    for c in BENCH["configs"]:
        assert (run.ROOT / c["file"]).is_file()
        assert json.loads((run.ROOT / c["file"]).read_text())["name"] \
            == c["name"]


def test_a_metric_without_workloads_reaches_every_cell():
    bench = copy.deepcopy(BENCH)
    bench["per_layer"].append({"name": "device_idle_share", "unit": "%"})
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.cell_metrics(bench, cell, True)]
        assert names.count("device_idle_share") == 2


# -- a whole run, sound and broken ---------------------------------------------


@pytest.mark.parametrize("cfg,mix,cell", [
    (TINY, tiny_chat(), CHAT_CELL), (TINY_MOE, tiny_agent(), AGENT_CELL),
    (TINY_POD, tiny_chat(), POD_CELL)],
    ids=["dense-chat", "moe-agent", "dense-pod-chat"])
def test_a_sound_run_is_correct(cfg, mix, cell):
    out = run_tiny(cfg, mix, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {
        m["name"] for m in run.cell_metrics(BENCH, cell, trace=False)}
    assert out["compiles_in_window"] == 0
    assert len(out["device"]["memory_peak_bytes_by_device"]) == cell["chips"]
    assert list(out)[-1] == "checks"


def test_a_pod_needs_as_many_chips_as_it_has_shards():
    with pytest.raises(run.BenchError, match="spans 4 chips"):
        run_tiny(TINY_POD, tiny_chat(), dict(POD_CELL, chips=2))


def _token_off_by_one(monkeypatch):
    from repro.kernels import ops
    from repro.models import transformer
    monkeypatch.setattr(
        transformer, "greedy_tokens",
        lambda logits, cfg: (ops.greedy_sample(logits, cfg.vocab_size) + 1)
        % cfg.vocab_size)


def _kv_never_written(monkeypatch):
    from repro.models import attention
    monkeypatch.setattr(attention, "paged_cache_write",
                        lambda pages, *a, **k: pages)


def _exchange_left_out(monkeypatch):
    """Each chip of a pod goes on with its own heads' share of the
    attention output, never summed with the other chips' shares."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import SERVE_AXIS, current_mesh
    from repro.models import attention

    def local_output(params, o):
        b, s, h, dh = o.shape

        def part(o_here, wo):
            n = o_here.shape[2] * dh
            rows = jax.lax.dynamic_slice_in_dim(
                wo, jax.lax.axis_index(SERVE_AXIS) * n, n)
            return o_here.reshape(b, s, n) @ rows

        return jax.shard_map(
            part, mesh=current_mesh(),
            in_specs=(P(None, None, SERVE_AXIS, None), P()), out_specs=P(),
            check_vma=False)(o, params["wo"])

    monkeypatch.setattr(attention, "_output", local_output)


@pytest.mark.parametrize("fault,cfg,cell", [
    (_token_off_by_one, TINY, CHAT_CELL),
    (_kv_never_written, TINY, CHAT_CELL),
    (_exchange_left_out, TINY_POD, POD_CELL)],
    ids=["token-altered", "state-unchanged", "exchange-left-out"])
def test_a_broken_served_path_is_not_correct(fault, cfg, cell, monkeypatch):
    fault(monkeypatch)
    out = run_tiny(cfg, tiny_chat(), cell)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        cfg["correct"]["max_logit_gap"]


def test_the_lower_precision_control_is_not_correct():
    """The reference in float8 in the program's place reads a widest gap
    over the limit that sound runs keep to."""
    mix = tiny_chat()
    plan = traffic.generate(mix, 256, 11, 2.0)
    served = []
    for r in plan.arrivals[:4]:
        toks = np.random.default_rng(len(r.prompt)).integers(
            0, 256, r.max_new_tokens).astype(np.int32)
        served.append(check.Served(r.prompt, toks))
    gaps = check.control_gaps(TINY, 11, served, 4, traffic.max_rows(mix),
                              mix["reply_tokens"]["max"])
    ok, checks = check.verdict(TINY, gaps, served)
    assert not ok
    assert checks["max_logit_gap"]["value"] > TINY["correct"]["max_logit_gap"]


def test_reference_is_the_same_with_layers_drawn_one_at_a_time():
    """The weights the program gets (all layers in one call) equal the
    ones the reference draws layer by layer."""
    from benchmarks.chip.adapters import qwen2 as adapter
    from benchmarks.chip.references import qwen2 as reference
    from benchmarks.chip.weights import base_key, layer_tensors, seed_words
    model = adapter.build(TINY)
    lo, hi = seed_words(2**40 + 3)
    params = adapter.make_params(TINY, model, lo, hi)
    _, specs = reference.weight_specs(TINY)
    one = layer_tensors(base_key(lo, hi), specs, 1)
    assert jnp.array_equal(params["layers"]["attn"]["wq"][1], one["q_proj"])
    assert jnp.array_equal(params["layers"]["mlp"]["w_down"][1],
                           one["down_proj"])
    gain = one["input_layernorm"].astype(jnp.float32)
    assert jnp.allclose(1.0 + params["layers"]["ln1"][1].astype(jnp.float32),
                        gain, atol=1e-2)
    assert jax.devices()[0].platform == "cpu"


def test_pod_weights_are_drawn_in_their_shards():
    """Drawn straight into the pod's placement, every weight has the value
    of the one-device draw, bit for bit, and the sharding the program
    serves it under; no device holds the whole tree."""
    from jax.sharding import NamedSharding

    from benchmarks.chip.adapters import qwen2 as adapter
    from benchmarks.chip.weights import seed_words
    from repro.distributed.sharding import serve_pspec, tp_mesh
    from repro.serving.engine import per_device_bytes
    model = adapter.build(TINY)
    lo, hi = seed_words(2**40 + 3)
    one = adapter.make_params(TINY, model, lo, hi)
    mesh = tp_mesh(4, jax.devices()[:4])
    pod = adapter.make_params(TINY, model, lo, hi, mesh=mesh)
    names = jax.tree_util.tree_leaves(
        model.param_names(), is_leaf=lambda x: isinstance(x, tuple))
    leaves = jax.tree_util.tree_leaves(pod)
    assert len(names) == len(leaves)
    for a, b, nm in zip(jax.tree_util.tree_leaves(one), leaves, names):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        want = NamedSharding(mesh, serve_pspec(nm, b.shape, mesh))
        assert b.sharding.is_equivalent_to(want, b.ndim), nm
    held = per_device_bytes(pod)
    assert sorted(held) == [d.id for d in jax.devices()[:4]]
    total = sum(x.nbytes for x in leaves)
    assert max(held.values()) < 0.6 * total
