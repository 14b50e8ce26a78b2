"""``chip_smoke.py``'s phases at tiny size on CPU devices, the refusal of
its entry point without a TPU, and the fixed compile-cache location."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache

from conftest import tiny_config
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _cfg(smoke, **kw):
    # Four KV heads, so the 4-way pod shards its KV pool too.
    base = dict(model=tiny_config(n_heads=8, n_kv_heads=4), n_requests=8,
                prompt_len=16, prefix_len=8, new_tokens=(8, 12),
                max_batch=2, block_size=4, ref_steps=3)
    base.update(kw)
    return smoke.SmokeConfig(**base)


def test_smoke_config_sizes(smoke):
    cfg = smoke.SmokeConfig(model=smoke.qwen2_7b_cut())
    m = cfg.model
    assert (m.n_layers, m.d_model, m.n_heads, m.n_kv_heads, m.dh, m.d_ff,
            m.vocab_size, m.qkv_bias) == (16, 3584, 28, 4, 128, 18944,
                                          152064, True)
    assert cfg.max_len % cfg.block_size == 0
    assert cfg.max_len >= cfg.prompt_len + cfg.new_tokens[1] - 1
    traffic = smoke.make_traffic(cfg)
    assert len(traffic) == 16
    assert all(len(p) == 512 and 32 <= n <= 64 for p, n in traffic)
    shared = [(p[:256] == traffic[0][0][:256]).all() for p, _ in traffic]
    assert sum(shared) == 8


def test_one_chip_phase_tiny(smoke):
    cfg = _cfg(smoke)
    info = smoke.phase_one_chip(cfg)
    assert info["shared_block_hits"] > 0
    assert len(info["ref_rel_err"]) == cfg.ref_steps + 1
    assert max(info["ref_rel_err"]) <= cfg.tol
    assert info["tokens_per_s"] > 0 and info["ttft_p50_s"] > 0


def test_reference_check_catches_wrong_logits(smoke):
    """The float32 comparison fails a reference that disagrees with the
    served path (here: a reference with another lm head)."""
    cfg = _cfg(smoke)
    model = smoke.build_model(cfg.model)
    params = smoke.make_params(model, cfg.seed)
    prompt = smoke.make_traffic(cfg)[0][0]
    assert max(smoke.reference_errors(cfg, model, params, prompt)) <= cfg.tol
    other_head = smoke.make_params(model, cfg.seed + 1)["head"]

    class OtherHeadReference(type(model)):
        def forward(self, p, tokens, **kw):
            return super().forward(dict(p, head=other_head), tokens, **kw)

    errs = smoke.reference_errors(cfg, OtherHeadReference(cfg.model),
                                  params, prompt)
    assert min(errs) > 10 * cfg.tol


def test_four_chip_phase_tiny(smoke):
    """Replicas on four host devices (with a cross-device migration) and
    a 4-way pod, against the one-device instance."""
    assert len(jax.devices()) >= 4  # conftest forces four host devices
    cfg = _cfg(smoke)
    info = smoke.phase_four_chips(cfg)
    assert sorted(info["replica_devices"]) == [0, 1, 2, 3]
    assert info["migrated_requests"] >= 1
    assert sorted(info["pod_bytes_by_device"]) == [0, 1, 2, 3]
    # With bf16 weights the pod is close to, not bitwise, one device.
    assert 0.0 <= info["pod_prefill_rel_err"] <= cfg.tol
    assert 0 < info["pod_identical_streams"] <= cfg.n_requests


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert "no TPU" in out.err
    for line in out.out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.fixture
def fresh_cache_config():
    """Restore JAX's cache settings (and drop its cache object) after a
    test that points the persistent cache somewhere."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_fixed_dir(tmp_path, monkeypatch, fresh_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    path = tmp_path / ".jax_cache"
    assert compile_cache.enable_compile_cache(path) == str(path)
    assert jax.config.jax_compilation_cache_dir == str(path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: x * 3 - 1)(jnp.arange(5.0)).block_until_ready()
    assert any(path.iterdir())


def test_compile_cache_env_wins(tmp_path, monkeypatch, fresh_cache_config):
    """With the variable set, JAX read it itself: nothing is set here."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache(tmp_path / "other") == str(
        tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / "other").exists()
