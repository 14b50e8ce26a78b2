"""Per-kernel tests: Pallas (interpret mode) and xla paths vs pure-jnp
oracles, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas
from repro.kernels.wkv6 import wkv6_pallas

# JAX-compile-heavy (Pallas-interpret kernel sweeps): excluded from tier-1, run via `-m slow`.
pytestmark = pytest.mark.slow


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.normal(size=shape), jnp.float32).astype(dtype)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


FLASH_SHAPES = [
    # (B, Sq, Sk, H, K, D, bq, bk)
    (1, 16, 16, 4, 4, 16, 8, 8),     # MHA
    (2, 32, 32, 8, 2, 32, 8, 16),    # GQA, rectangular blocks
    (1, 64, 64, 4, 1, 64, 64, 32),   # MQA, single q block
    (2, 24, 24, 6, 3, 8, 24, 8),     # odd head count
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (True, 9),
                                           (False, None)])
def test_flash_pallas_vs_ref(shape, dtype, causal, window):
    b, sq, sk, h, k, d, bq, bk = shape
    rng = np.random.default_rng(hash((shape, causal, window or 0)) % 2**32)
    q = _rand(rng, (b, sq, h, d), dtype)
    kk = _rand(rng, (b, sk, k, d), dtype)
    v = _rand(rng, (b, sk, k, d), dtype)
    out = flash_attention_pallas(q, kk, v, causal=causal, window=window,
                                 block_q=bq, block_k=bk)
    expected = ref.mha_reference(q, kk, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_xla_vs_ref_sweep(dtype):
    rng = np.random.default_rng(3)
    for (b, sq, h, k, d) in [(1, 16, 4, 2, 16), (2, 64, 8, 8, 32)]:
        q = _rand(rng, (b, sq, h, d), dtype)
        kk = _rand(rng, (b, sq, k, d), dtype)
        v = _rand(rng, (b, sq, k, d), dtype)
        out = ops.flash_attention(q, kk, v, causal=True, block_q=16,
                                  block_k=16, backend="xla")
        expected = ref.mha_reference(q, kk, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expected, np.float32),
                                   **_tol(dtype))


def test_flash_q_offset_matches_suffix():
    """q_offset positions queries at the cache tail (chunked prefill)."""
    rng = np.random.default_rng(5)
    b, s, h, k, d = 1, 32, 4, 2, 16
    q = _rand(rng, (b, s, h, d), jnp.float32)
    kk = _rand(rng, (b, s, k, d), jnp.float32)
    v = _rand(rng, (b, s, k, d), jnp.float32)
    full = ref.mha_reference(q, kk, v, causal=True)
    tail = ops.flash_attention(q[:, 16:], kk, v, causal=True, q_offset=16,
                               block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(full[:, 16:]),
                               rtol=1e-5, atol=1e-5)


DECODE_SHAPES = [
    # (B, S, H, K, D, bs)
    (2, 32, 8, 2, 16, 8),
    (1, 128, 4, 4, 32, 64),
    (3, 64, 4, 1, 64, 64),
]


@pytest.mark.parametrize("shape", DECODE_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [None, 17])
def test_decode_pallas_vs_ref(shape, dtype, window):
    b, s, h, k, d, bs = shape
    rng = np.random.default_rng(hash((shape, window or 0)) % 2**32)
    q = _rand(rng, (b, 1, h, d), dtype)
    kc = _rand(rng, (b, s, k, d), dtype)
    vc = _rand(rng, (b, s, k, d), dtype)
    cache_len = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
    out = decode_attention_pallas(q, kc, vc, cache_len, window=window,
                                  block_s=bs)
    expected = ref.decode_reference(q, kc, vc, cache_len, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("shape", DECODE_SHAPES)
def test_decode_quant_pallas_vs_dequant_ref(shape):
    """int8-KV decode kernel (§Perf D): pallas(int8) == ref(dequantized)."""
    from repro.kernels.decode_attention import decode_attention_quant_pallas
    from repro.models.attention import kv_quantize

    b, s, h, k, d, bs = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    q = _rand(rng, (b, 1, h, d), jnp.bfloat16)
    kc = _rand(rng, (b, s, k, d), jnp.bfloat16)
    vc = _rand(rng, (b, s, k, d), jnp.bfloat16)
    k8, ks = kv_quantize(kc)
    v8, vs = kv_quantize(vc)
    cache_len = jnp.asarray(rng.integers(1, s + 1, (b,)), jnp.int32)
    out = decode_attention_quant_pallas(q, k8, v8, ks, vs, cache_len,
                                        block_s=bs)
    # Oracle: dequantize, then the bf16 reference — isolates kernel math.
    deq = lambda c, sc: (c.astype(jnp.float32)
                         * sc.astype(jnp.float32)).astype(jnp.bfloat16)
    expected = ref.decode_reference(q, deq(k8, ks), deq(v8, vs), cache_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(jnp.bfloat16))
    # And the ops wrapper dispatches both backends consistently.
    out_xla = ops.decode_attention_quant(q, k8, v8, ks, vs, cache_len,
                                         backend="xla")
    np.testing.assert_allclose(np.asarray(out_xla, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(jnp.bfloat16))


PAGED_SHAPES = [
    # (B, H, K, D, bs, M, N)  — M table slots/seq, N physical blocks
    (2, 8, 2, 16, 8, 4, 12),
    (3, 4, 4, 32, 16, 3, 16),
    (1, 4, 1, 64, 32, 2, 5),
]


def _paged_tables(rng, b, m, n, bs):
    """Disjoint per-sequence block lists + valid lengths, null-padded."""
    perm = rng.permutation(np.arange(1, n))  # never the null block 0
    tables = np.zeros((b, m), np.int32)
    cache_len = np.zeros((b,), np.int32)
    take = 0
    for i in range(b):
        used = int(rng.integers(1, m + 1))
        tables[i, :used] = perm[take:take + used]
        take += used
        cache_len[i] = rng.integers(max((used - 1) * bs, 1), used * bs + 1)
    return jnp.asarray(tables), jnp.asarray(cache_len)


@pytest.mark.parametrize("shape", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_pallas_vs_gather_ref(shape, dtype):
    """Block-table walk == gather-then-dense-reference, ragged lengths."""
    b, h, k, d, bs, m, n = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    q = _rand(rng, (b, 1, h, d), dtype)
    kp = _rand(rng, (n, bs, k, d), dtype)
    vp = _rand(rng, (n, bs, k, d), dtype)
    tables, cache_len = _paged_tables(rng, b, m, n, bs)
    out = ops.paged_decode_attention(q, kp, vp, tables, cache_len,
                                     backend="pallas")
    gk = ops._gather_pages(kp, tables)
    gv = ops._gather_pages(vp, tables)
    expected = ref.decode_reference(q, gk, gv, cache_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(dtype))
    out_xla = ops.paged_decode_attention(q, kp, vp, tables, cache_len,
                                         backend="xla")
    np.testing.assert_allclose(np.asarray(out_xla, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(dtype))


@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_decode_quant_pallas_vs_dequant_ref(shape):
    """int8-KV paged kernel: pallas(int8 pages) == ref(dequantized gather)."""
    from repro.models.attention import kv_quantize

    b, h, k, d, bs, m, n = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    q = _rand(rng, (b, 1, h, d), jnp.bfloat16)
    kp = _rand(rng, (n, bs, k, d), jnp.bfloat16)
    vp = _rand(rng, (n, bs, k, d), jnp.bfloat16)
    k8, ks = kv_quantize(kp)
    v8, vs = kv_quantize(vp)
    tables, cache_len = _paged_tables(rng, b, m, n, bs)
    out = ops.paged_decode_attention_quant(q, k8, v8, ks, vs, tables,
                                           cache_len, backend="pallas")
    deq = lambda c, sc: (c.astype(jnp.float32)
                         * sc.astype(jnp.float32)).astype(jnp.bfloat16)
    expected = ref.decode_reference(
        q, ops._gather_pages(deq(k8, ks), tables),
        ops._gather_pages(deq(v8, vs), tables), cache_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32),
                               **_tol(jnp.bfloat16))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_paged_decode_reads_one_layer_of_stacked_pools(backend, quant):
    """``layer=`` on stacked (L, N, ...) pools reads exactly what the same
    call reads from that layer's (N, ...) pages."""
    from repro.models.attention import kv_quantize

    b, h, k, d, bs, m, n = PAGED_SHAPES[0]
    rng = np.random.default_rng(7)
    q = _rand(rng, (b, 1, h, d), jnp.bfloat16)
    pools = [_rand(rng, (3, n, bs, k, d), jnp.bfloat16) for _ in range(2)]
    if quant:
        pools = [x for p in pools for x in kv_quantize(p)]
        pools = [pools[0], pools[2], pools[1], pools[3]]  # k, v, ks, vs
        fn = ops.paged_decode_attention_quant
    else:
        fn = ops.paged_decode_attention
    tables, cache_len = _paged_tables(rng, b, m, n, bs)
    layer = jnp.int32(1)
    got = fn(q, *pools, tables, cache_len, layer=layer, backend=backend)
    want = fn(q, *(p[1] for p in pools), tables, cache_len, backend=backend)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


WKV_SHAPES = [
    # (B, S, H, D, bt)
    (2, 16, 2, 8, 8),
    (1, 32, 4, 16, 16),
    (2, 24, 1, 32, 24),
]


@pytest.mark.parametrize("shape", WKV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wkv6_pallas_vs_ref(shape, dtype):
    b, s, h, d, bt = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    r = _rand(rng, (b, s, h, d), dtype)
    k = _rand(rng, (b, s, h, d), dtype)
    v = _rand(rng, (b, s, h, d), dtype)
    w = (-jnp.exp(_rand(rng, (b, s, h, d), jnp.float32) * 0.3) - 0.01
         ).astype(dtype)
    u = _rand(rng, (h, d), dtype)
    st = _rand(rng, (b, h, d, d), jnp.float32)
    out, s_t = wkv6_pallas(r, k, v, w, u, st, block_t=bt)
    eo, es = ref.wkv6_reference(r, k, v, w, u, st)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(eo, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(es),
                               **_tol(dtype))


def test_wkv6_chunking_invariance():
    """Chunked scan must be exactly associative across chunk boundaries."""
    rng = np.random.default_rng(11)
    b, s, h, d = 1, 32, 2, 8
    r = _rand(rng, (b, s, h, d), jnp.float32)
    k = _rand(rng, (b, s, h, d), jnp.float32)
    v = _rand(rng, (b, s, h, d), jnp.float32)
    w = -jnp.exp(_rand(rng, (b, s, h, d), jnp.float32) * 0.3) - 0.01
    u = _rand(rng, (h, d), jnp.float32)
    st = jnp.zeros((b, h, d, d), jnp.float32)
    o1, s1 = wkv6_pallas(r, k, v, w, u, st, block_t=32)
    o2, s2 = wkv6_pallas(r, k, v, w, u, st, block_t=8)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5,
                               atol=1e-6)


SSM_SHAPES = [
    # (B, S, H, D, N, bt)
    (2, 16, 2, 8, 4, 8),
    (1, 32, 4, 16, 8, 16),
]


@pytest.mark.parametrize("shape", SSM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_pallas_vs_ref(shape, dtype):
    b, s, h, d, n, bt = shape
    rng = np.random.default_rng(hash(shape) % 2**32)
    x = _rand(rng, (b, s, h, d), dtype)
    dt = jnp.abs(_rand(rng, (b, s, h), jnp.float32) * 0.1).astype(dtype)
    a_log = _rand(rng, (h, n), jnp.float32) * 0.2
    bm = _rand(rng, (b, s, h, n), dtype)
    cm = _rand(rng, (b, s, h, n), dtype)
    st = _rand(rng, (b, h, d, n), jnp.float32)
    y, s_t = ssm_scan_pallas(x, dt, a_log, bm, cm, st, block_t=bt)
    ey, es = ref.ssm_reference(x, dt, a_log, bm, cm, st)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ey, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(s_t), np.asarray(es), **_tol(dtype))


def test_state_carry_across_calls_matches_single_call():
    """Running the kernel on two halves with carried state == one call."""
    rng = np.random.default_rng(13)
    b, s, h, d, n = 1, 16, 2, 8, 4
    x = _rand(rng, (b, s, h, d), jnp.float32)
    dt = jnp.abs(_rand(rng, (b, s, h), jnp.float32) * 0.1)
    a_log = _rand(rng, (h, n), jnp.float32) * 0.2
    bm = _rand(rng, (b, s, h, n), jnp.float32)
    cm = _rand(rng, (b, s, h, n), jnp.float32)
    st = jnp.zeros((b, h, d, n), jnp.float32)
    y_full, s_full = ssm_scan_pallas(x, dt, a_log, bm, cm, st, block_t=8)
    y1, s1 = ssm_scan_pallas(x[:, :8], dt[:, :8], a_log, bm[:, :8],
                             cm[:, :8], st, block_t=8)
    y2, s2 = ssm_scan_pallas(x[:, 8:], dt[:, 8:], a_log, bm[:, 8:],
                             cm[:, 8:], s1, block_t=8)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-5, atol=1e-6)
