"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret backend on
CPU) against its pure-jnp oracle in ref.py, dispatched through the registry
(the whole module runs inside a ``use_backend("interpret")`` scope)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.dispatch import use_backend


# module-scoped: Hypothesis' function_scoped_fixture health check rejects
# function-scoped autouse fixtures around @given tests
@pytest.fixture(autouse=True, scope="module")
def _interpret_backend():
    with use_backend("interpret"):
        yield

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, key=KEY, scale=1.0):
    x = jax.random.normal(key, shape, jnp.float32) * scale
    return x.astype(dtype)


# --------------------------------------------------------------------------
# streaming GEMM + fused in-stream epilogue (paper C1 + C5b)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (100, 96, 130), (128, 128, 128),
                                   (37, 200, 65), (256, 64, 512)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_shapes_dtypes(m, k, n, dtype):
    x = _rand((m, k), dtype)
    w = _rand((k, n), dtype, jax.random.PRNGKey(1))
    got = ops.gemm(x, w)
    want = ref.gemm_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("act", [None, "gelu", "silu"])
@pytest.mark.parametrize("scale", [1.0, 0.125])
def test_gemm_fused_epilogue(act, scale):
    x = _rand((64, 48))
    w = _rand((48, 96), key=jax.random.PRNGKey(1))
    b = _rand((96,), key=jax.random.PRNGKey(2))
    got = ops.gemm(x, w, bias=b, scale=scale, act=act)
    want = ref.gemm_ref(x, w, bias=b, scale=scale, act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gemm_block_shapes():
    x = _rand((200, 100))
    w = _rand((100, 150), key=jax.random.PRNGKey(1))
    want = ref.gemm_ref(x, w)
    for bm, bn, bk in [(64, 64, 64), (128, 256, 32), (32, 32, 128)]:
        got = ops.gemm(x, w, block_m=bm, block_n=bn,
                       block_k=bk)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# flash attention (paper §II-C uses FlashAttention-2)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("sq,skv,d", [(64, 64, 16), (60, 60, 32),
                                      (128, 256, 16), (33, 95, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(sq, skv, d, causal):
    if causal and sq != skv:
        pytest.skip("causal requires aligned q/kv here")
    q = _rand((4, sq, d), scale=0.5)
    k = _rand((4, skv, d), key=jax.random.PRNGKey(1), scale=0.5)
    v = _rand((4, skv, d), key=jax.random.PRNGKey(2))
    got = ops.flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [0, 16, 64])
@pytest.mark.parametrize("cap", [0.0, 20.0])
def test_flash_attention_window_softcap(window, cap):
    q = _rand((2, 96, 32), scale=0.5)
    k = _rand((2, 96, 32), key=jax.random.PRNGKey(1), scale=0.5)
    v = _rand((2, 96, 32), key=jax.random.PRNGKey(2))
    got = ops.flash_attention(q, k, v, causal=True, window=window, cap=cap,
                              block_q=32, block_k=32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window,
                                   cap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("g", [1, 2, 4])
def test_flash_attention_gqa(g):
    """BH = g * BK (grouped query heads share KV heads)."""
    q = _rand((2 * g, 64, 16), scale=0.5)
    k = _rand((2, 64, 16), key=jax.random.PRNGKey(1), scale=0.5)
    v = _rand((2, 64, 16), key=jax.random.PRNGKey(2))
    got = ops.flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    kr, vr = jnp.repeat(k, g, 0), jnp.repeat(v, g, 0)
    want = ref.flash_attention_ref(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_scale():
    q = _rand((1, 32, 16), scale=0.5)
    k = _rand((1, 32, 16), key=jax.random.PRNGKey(1), scale=0.5)
    v = _rand((1, 32, 16), key=jax.random.PRNGKey(2))
    got = ops.flash_attention(q, k, v, causal=True, scale=0.0833,
                              block_q=16, block_k=16)
    want = ref.flash_attention_ref(q, k, v, causal=True, scale=0.0833)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# LRU / SSM diagonal recurrence scan
# --------------------------------------------------------------------------
@pytest.mark.parametrize("b,l,d", [(1, 16, 8), (2, 50, 40), (3, 128, 512),
                                   (2, 100, 130)])
def test_lru_scan_shapes(b, l, d):
    a = jax.random.uniform(KEY, (b, l, d), minval=0.5, maxval=0.999)
    x = _rand((b, l, d), key=jax.random.PRNGKey(1))
    got = ops.lru_scan(a, x)
    want = ref.lru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunk", [8, 32, 256])
def test_lru_scan_chunking_invariant(chunk):
    """Chunked kernel == unchunked reference for any chunk length."""
    a = jax.random.uniform(KEY, (2, 100, 64), minval=0.3, maxval=0.99)
    x = _rand((2, 100, 64), key=jax.random.PRNGKey(1))
    got = ops.lru_scan(a, x, chunk=chunk)
    want = ref.lru_scan_ref(a, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# packed irregular streams (paper C5c)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("rows,width,m", [(64, 32, 37), (4096, 64, 2048),
                                          (100, 130, 333)])
@pytest.mark.parametrize("pack", [4, 8])
def test_packed_gather(rows, width, m, pack):
    table = _rand((rows, width))
    idx = jax.random.randint(KEY, (m,), 0, rows)
    got = ops.packed_gather_rows(table, idx, pack=pack)
    want = ref.gather_rows_ref(table, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_packed_gather_unsorted():
    table = _rand((128, 16))
    idx = jax.random.randint(KEY, (50,), 0, 128)
    got = ops.packed_gather_rows(table, idx, sort=False)
    want = ref.gather_rows_ref(table, idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 63), min_size=1, max_size=200))
def test_packed_gather_property(idx_list):
    """Property: packed+coalesced gather == table[idx] for any index stream
    (duplicates, any order, any length)."""
    table = _rand((64, 8))
    idx = jnp.asarray(idx_list, jnp.int32)
    got = ops.packed_gather_rows(table, idx)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(table)[np.asarray(idx)])


# --------------------------------------------------------------------------
# in-stream DMA ops (paper C5b)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("m,d", [(16, 8), (100, 64), (1000, 256)])
@pytest.mark.parametrize("scale,shift", [(1.0, 0.0), (2.5, -1.0)])
def test_instream_scale_reduce(m, d, scale, shift):
    x = _rand((m, d))
    got_y, got_s = ops.instream_scale_reduce(x, scale=scale, shift=shift)
    want_y, want_s = ref.instream_scale_reduce_ref(x, scale=scale, shift=shift)
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(got_s), float(want_s),
                               rtol=1e-4, atol=1e-2)


@settings(max_examples=20, deadline=None)
@given(st.floats(-4, 4), st.floats(-2, 2))
def test_instream_property(scale, shift):
    x = _rand((33, 17))
    got_y, got_s = ops.instream_scale_reduce(x, scale=scale, shift=shift)
    np.testing.assert_allclose(np.asarray(got_y),
                               np.asarray(x) * scale + shift,
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(got_s),
                               float((np.asarray(x) * scale + shift).sum()),
                               rtol=1e-3, atol=5e-2)


# --------------------------------------------------------------------------
# paged decode attention over layer-stacked pools
# --------------------------------------------------------------------------
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kv", ["float32", "bfloat16", "int8", "fp8"])
def test_paged_attention_stacked_layer(kv, layer):
    """The kernel reads layer ``layer`` of a (L, N, page, K, D) pool through
    its index maps: it matches the oracle on that layer's own pool, passed
    as the leading-1 stack at layer 0. The other layers hold different rows,
    so a wrong layer index cannot pass."""
    from repro.quant import quantize_kv

    L, B, K, G, D, N, page, P = 3, 3, 2, 4, 16, 9, 8, 4
    keys = jax.random.split(KEY, 4)
    q = _rand((B, K, G, D), key=keys[0], scale=0.5)
    kp = _rand((L, N, page, K, D), key=keys[1], scale=0.5)
    vp = _rand((L, N, page, K, D), key=keys[2])
    tables = jax.random.randint(keys[3], (B, P), 0, N, jnp.int32)
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    if kv in ("int8", "fp8"):
        (kp, ks), (vp, vs) = quantize_kv(kp, kv), quantize_kv(vp, kv)
        q = q.astype(jnp.float32)
        scales, one = (ks, vs), (ks[layer][None], vs[layer][None])
    else:
        q, kp, vp = (a.astype(kv) for a in (q, kp, vp))
        scales = one = ()
    got = ops.paged_attention(q, kp, vp, tables, lengths, layer, *scales,
                              cap=30.0)
    want = ref.paged_attention_ref(q, kp[layer][None], vp[layer][None],
                                   tables, lengths, 0, *one, cap=30.0)
    tol = 3e-2 if kv == "bfloat16" else 5e-4
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
