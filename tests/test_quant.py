"""Quantization subsystem: absmax quantizers, QuantTensor containers,
gemm_wq registry parity, quantized paged KV, engine integration, sizing,
checkpoint round-trip, and the roofline byte terms."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import quant
from repro.configs import LayerSpec, ModelConfig, get_arch, reduced
from repro.kernels import ops
from repro.kernels.dispatch import registry, resolve_backend, use_backend
from repro.models import decode_step, forward, init, logits_fn
from repro.models.cache import (init_cache, kv_block_bytes, kv_bytes,
                                n_blocks_for_bytes)
from repro.quant import QuantTensor
from repro.serve import Request, ServeEngine

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, seed=0, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * scale
    return x.astype(dtype)


def _cfg(**kw):
    base = dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2,
                head_dim=32, d_ff=256, vocab_size=256, dtype="float32",
                param_dtype="float32")
    base.update(kw)
    return reduced(get_arch("qwen3-0.6b")).replace(**base)


# --------------------------------------------------------------------------
# quantizers
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [("int8", 1.5 / 127), ("fp8", 0.08)])
@pytest.mark.parametrize("block", [0, 16])
def test_weight_roundtrip_error_bound(dtype, tol, block):
    w = _rand((64, 48))
    q, scales = quant.quantize_weight(w, dtype, block=block)
    back = quant.dequantize_weight(q, scales)
    # absmax quantization error is bounded by the scale step per block
    amax = np.abs(np.asarray(w)).max()
    assert np.abs(np.asarray(back) - np.asarray(w)).max() <= tol * amax
    assert scales.dtype == jnp.float16
    assert scales.shape == ((64 // block if block else 1), 48)


def test_per_block_scales_beat_per_channel_on_outliers():
    w = _rand((64, 8), scale=0.05)
    w = w.at[0, :].set(8.0)            # one outlier row blows the amax
    per_ch = quant.dequantize_weight(*quant.quantize_weight(w, "int8"))
    per_bl = quant.dequantize_weight(
        *quant.quantize_weight(w, "int8", block=8))
    # outside the outlier's scale block the per-block error collapses
    err_ch = np.abs(np.asarray(per_ch - w))[8:].max()
    err_bl = np.abs(np.asarray(per_bl - w))[8:].max()
    assert err_bl < err_ch / 4


def test_embed_axis_per_row_scales():
    t = _rand((32, 16))
    qt = quant.quantize_tensor(t, "int8", axis=-1)
    assert qt.scales.shape == (32, 1)
    err = np.abs(np.asarray(qt.dequantize()) - np.asarray(t))
    row_amax = np.abs(np.asarray(t)).max(axis=1, keepdims=True)
    assert (err <= 1.5 / 127 * row_amax + 1e-6).all()


def test_kv_row_quantize_roundtrip():
    x = _rand((5, 3, 16), scale=0.7)
    q, s = quant.quantize_kv(x, "int8")
    assert q.dtype == jnp.int8 and s.dtype == jnp.float16
    assert s.shape == (5, 3)
    back = quant.dequantize_kv(q, s)
    amax = np.abs(np.asarray(x)).max(-1, keepdims=True)
    assert (np.abs(np.asarray(back) - np.asarray(x))
            <= 1.5 / 127 * amax + 1e-6).all()


def test_quantize_int8_shared_with_collectives():
    """One absmax implementation serves the gradient channel too."""
    from repro.core import collectives

    assert collectives._quantize_int8 is quant.quantize_int8
    x = _rand((33,))
    q, scale = quant.quantize_int8(x)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(q, np.float32) * float(scale),
                               np.asarray(x), atol=float(scale) * 0.51)


def test_dtype_aliases_and_bytes():
    assert quant.canonical_dtype("fp8") == "float8_e4m3fn"
    assert quant.canonical_dtype("int4") == "int4"
    assert quant.dtype_bytes("int8") == 1
    assert quant.dtype_bytes("fp8") == 1
    assert quant.dtype_bytes("int4") == 0.5
    assert quant.dtype_bytes("bfloat16") == 2
    # int4 is weight-only: valid for weight_dtype, rejected for kv_dtype
    assert ModelConfig(weight_dtype="int4").weight_dtype == "int4"
    with pytest.raises(ValueError):
        ModelConfig(kv_dtype="int4")
    with pytest.raises(ValueError):
        ModelConfig(kv_dtype="fp16")
    with pytest.raises(ValueError):
        quant.quantize_kv(jnp.ones((2, 4)), "int4")
    with pytest.raises(ValueError):
        ModelConfig(weight_density=0.0)
    assert ModelConfig(weight_density=0.5).weight_density == 0.5


# --------------------------------------------------------------------------
# edge cases: zero rows (scale floor) and fp8 saturation
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["int8", "fp8", "int4"])
def test_all_zero_input_roundtrips_to_zero(dtype):
    """Regression: an all-zero block used to produce a 0.0 (or underflowed)
    scale whose reciprocal made NaN codes; the amax floor keeps the
    round-trip exactly zero and finite everywhere."""
    w = jnp.zeros((32, 16), jnp.float32)
    q, s = quant.quantize_weight(w, dtype, block=16)
    assert np.isfinite(np.asarray(s, np.float32)).all()
    assert (np.asarray(s, np.float32) > 0).all()
    back = np.asarray(quant.dequantize_weight(
        q, s, pack=2 if dtype == "int4" else 1))
    assert np.isfinite(back).all() and (back == 0).all()
    if dtype != "int4":           # KV pools are int8/fp8 only
        kv = jnp.zeros((2, 8, 16), jnp.float32)   # an all-zero KV page
        kq, ks = quant.quantize_kv(kv, dtype)
        kb = np.asarray(quant.dequantize_kv(kq, ks), np.float32)
        assert np.isfinite(kb).all() and (kb == 0).all()
    qz, sz = quant.quantize_int8(jnp.zeros((8,)))
    assert float(sz) > 0 and not np.isnan(np.asarray(qz, np.float32)).any()


def test_fp8_cast_saturates_instead_of_nan():
    """Regression: a raw ``.astype(float8_e4m3fn)`` NaNs past ~±464 on CPU;
    the quantizer clips to ±448 before casting, so outliers saturate."""
    from repro.quant.tensor import _cast_q

    x = jnp.asarray([448.0, -448.0, 464.0, 1e4, -1e38], jnp.float32)
    out = np.asarray(_cast_q(x, "float8_e4m3fn"), np.float32)
    assert np.isfinite(out).all(), out
    np.testing.assert_array_equal(out, [448.0, -448.0, 448.0, 448.0, -448.0])
    # and through the public quantizer: a wild outlier row stays finite
    w = _rand((16, 8)).at[0, 0].set(3e4)
    back = quant.dequantize_weight(*quant.quantize_weight(w, "fp8", block=8))
    assert np.isfinite(np.asarray(back)).all()


# --------------------------------------------------------------------------
# int4: nibble packing, containers, gemm_wq
# --------------------------------------------------------------------------
def test_int4_pack_unpack_roundtrip():
    from repro.quant import pack_int4, unpack_int4

    codes = jnp.asarray(np.random.default_rng(0).integers(-8, 8, (12, 6)),
                        jnp.int8)
    packed = pack_int4(codes, axis=0)
    assert packed.shape == (6, 6) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed, axis=0)),
                                  np.asarray(codes))
    with pytest.raises(ValueError):
        pack_int4(codes[:11], axis=0)      # odd axis length


@pytest.mark.parametrize("block", [0, 16, 32])
def test_int4_weight_roundtrip_bound_and_bytes(block):
    w = _rand((64, 48))
    qt = quant.quantize_tensor(w, "int4", block=block)
    assert qt.pack == 2 and qt.q.shape == (32, 48)   # two nibbles per byte
    assert qt.shape == (64, 48)                      # logical shape
    back = np.asarray(qt.dequantize())
    amax = np.abs(np.asarray(w)).max()
    assert np.abs(back - np.asarray(w)).max() <= 1.5 / 7 * amax
    if block == 32:
        bf16_bytes = w.size * 2
        assert qt.nbytes / bf16_bytes <= 0.30, qt.nbytes / bf16_bytes


def test_int4_quant_tensor_pytree_and_legacy_aux():
    qt = quant.quantize_tensor(_rand((16, 8)), "int4", block=8)
    leaves, treedef = jax.tree_util.tree_flatten(qt)
    rt = jax.tree_util.tree_unflatten(treedef, leaves)
    assert rt.pack == 2 and rt.axis == qt.axis
    # pre-pack checkpoints serialized a bare-int aux (axis only)
    legacy = QuantTensor.tree_unflatten(-2, (qt.q, qt.scales))
    assert legacy.pack == 1 and legacy.axis == -2


@pytest.mark.parametrize("shape,block", [((48, 40, 56), 10), ((33, 64, 17), 16),
                                         ((8, 128, 8), 32)])
def test_gemm_wq_int4_kernel_matches_ref(shape, block):
    M, K, N = shape
    x = _rand((M, K))
    qt = quant.quantize_tensor(_rand((K, N), seed=1), "int4", block=block)
    assert qt.q.shape[0] == K // 2
    exact = np.asarray(x @ qt.dequantize())
    with use_backend("ref"):
        want = ops.gemm_wq(x, qt.q, qt.scales)
    with use_backend("interpret"):
        got = ops.gemm_wq(x, qt.q, qt.scales)
    np.testing.assert_allclose(np.asarray(want), exact, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gemm_wq_int4_negotiation():
    """Packed weights (K/2 rows) select the Pallas kernel when the scale
    blocking splits into even tiles, and fall back to the dequantize ref
    oracle otherwise — never a silent wrong-shape contraction."""
    x = _rand((8, 40))
    qt = quant.quantize_tensor(_rand((40, 16), seed=1), "int4", block=10)
    req = registry.request("gemm_wq", x, qt.q, qt.scales)
    impl = registry.select("gemm_wq", req, resolve_backend("interpret"))
    assert impl.name == "pallas"      # 40/4=10 blocks? K//nb=10 even
    # odd rows-per-scale-block (K//nb = 5) cannot tile packed bytes evenly
    qt5 = quant.quantize_tensor(_rand((40, 16), seed=1), "int4", block=5)
    req5 = registry.request("gemm_wq", x, qt5.q, qt5.scales)
    assert registry.select("gemm_wq", req5,
                           resolve_backend("interpret")).name == "ref"
    with use_backend("interpret"):     # ref still computes the right thing
        out = ops.gemm_wq(x, qt5.q, qt5.scales)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(x @ qt5.dequantize()),
                               rtol=1e-5, atol=1e-5)


def test_quantize_params_int4_bytes_and_forward():
    cfg = _cfg(weight_dtype="int4", quant_block=32)
    params = init(jax.random.PRNGKey(0), cfg)
    qp = quant.quantize_params(params, cfg)
    qt = qp["blocks"][0]["attn"]["q_proj"]["kernel"]
    assert isinstance(qt, QuantTensor) and qt.pack == 2
    assert qt.shape == params["blocks"][0]["attn"]["q_proj"]["kernel"].shape
    ratio = quant.param_bytes(qp) / quant.param_bytes(params)
    assert ratio <= 0.30 * 2, ratio    # fp32 baseline here (2x bf16 target)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    want, _, _ = forward(params, cfg, toks)
    got, _, _ = forward(qp, cfg, toks)
    w, g = np.asarray(want), np.asarray(got)
    rel = np.linalg.norm(g - w) / np.linalg.norm(w)
    # random-init hidden states at 4 bits drift hard (~0.21 per-weight step
    # compounding over layers); the trained-model accuracy gate lives in
    # benchmarks/quant_accuracy.py (teacher-forced match >= 0.95)
    assert rel < 0.6, rel
    with use_backend("interpret"):     # kernel path agrees with XLA dequant
        got_k, _, _ = forward(qp, cfg, toks)
    np.testing.assert_allclose(np.asarray(got_k), g, rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# property: round-trip bound across the whole ladder
# --------------------------------------------------------------------------
from tests._hyp import given, settings, st  # noqa: E402


def _roundtrip_case(dtype, nblocks, rows, n, regime, seed):
    """|dequant(quant(w)) - w| <= step * block_amax for every ladder rung,
    including all-zero rows, denormal rows, and single-element blocks."""
    rows = rows * 2 if dtype == "int4" else rows   # packing needs even K
    k = nblocks * rows
    rng = np.random.default_rng(seed)
    w = rng.standard_normal((k, n)).astype(np.float32)
    if regime == "zero_rows":
        w[:: max(1, k // 2)] = 0.0
    elif regime == "denormal":
        w[0] = 1e-42                    # below fp32 normal range
    elif regime == "outlier":
        w[0, 0] = 3e4
    q, s = quant.quantize_weight(jnp.asarray(w), dtype, block=rows)
    sf = np.asarray(s, np.float32)
    assert np.isfinite(sf).all() and (sf > 0).all()
    back = np.asarray(quant.dequantize_weight(
        q, s, pack=2 if dtype == "int4" else 1), np.float32)
    assert np.isfinite(back).all()
    # rounding half-step + fp16 scale-storage error, per block amax
    step = {"int8": 1.5 / 127, "fp8": 0.08, "int4": 1.5 / 7}[dtype]
    amax = np.abs(w).reshape(nblocks, rows, n).max(axis=1, keepdims=True)
    bound = np.broadcast_to(step * amax + 1e-5,
                            (nblocks, rows, n)).reshape(k, n)
    # the amax floor means tiny blocks round to zero rather than scale up
    bound = np.maximum(bound, 2e-4)
    assert (np.abs(back - w) <= bound).all()


@pytest.mark.property
@settings(max_examples=40, deadline=None)
@given(st.sampled_from(["int8", "fp8", "int4"]),
       st.integers(1, 6),                    # scale blocks along K
       st.integers(1, 5),                    # rows per scale block (x2 int4)
       st.integers(1, 8),                    # N
       st.sampled_from(["normal", "zero_rows", "denormal", "outlier"]),
       st.integers(0, 2 ** 31 - 1))
def test_quantize_roundtrip_bound_property(dtype, nblocks, rows, n, regime,
                                           seed):
    _roundtrip_case(dtype, nblocks, rows, n, regime, seed)


@pytest.mark.property
@pytest.mark.parametrize("dtype", ["int8", "fp8", "int4"])
@pytest.mark.parametrize("regime", ["normal", "zero_rows", "denormal",
                                    "outlier"])
def test_quantize_roundtrip_bound_seeded(dtype, regime):
    """Seeded fallback of the same driver: keeps the round-trip bound alive
    on containers without hypothesis (where @given-tests skip)."""
    rng = np.random.default_rng(hash((dtype, regime)) % (2 ** 31))
    for _ in range(10):
        _roundtrip_case(dtype, int(rng.integers(1, 7)),
                        int(rng.integers(1, 6)), int(rng.integers(1, 9)),
                        regime, int(rng.integers(0, 2 ** 31 - 1)))


# --------------------------------------------------------------------------
# gemm_wq through the registry
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["int8", "fp8"])
@pytest.mark.parametrize("shape,block", [((48, 40, 56), 0), ((48, 40, 56), 10),
                                         ((33, 64, 17), 16), ((8, 128, 8), 32)])
def test_gemm_wq_kernel_matches_ref(dtype, shape, block):
    M, K, N = shape
    x = _rand((M, K))
    qt = quant.quantize_tensor(_rand((K, N), seed=1), dtype, block=block)
    exact = np.asarray(x @ qt.dequantize())
    with use_backend("ref"):
        want = ops.gemm_wq(x, qt.q, qt.scales)
    with use_backend("interpret"):
        got = ops.gemm_wq(x, qt.q, qt.scales)
    np.testing.assert_allclose(np.asarray(want), exact, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gemm_wq_bias_act_epilogue_parity():
    x = _rand((20, 32))
    qt = quant.quantize_tensor(_rand((32, 24), seed=1), "int8", block=8)
    bias = _rand((24,), seed=2)
    with use_backend("ref"):
        want = ops.gemm_wq(x, qt.q, qt.scales, bias, scale=0.5, act="gelu")
    with use_backend("interpret"):
        got = ops.gemm_wq(x, qt.q, qt.scales, bias, scale=0.5, act="gelu")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gemm_wq_kernel_selected_and_negotiates_down():
    x = _rand((16, 32))
    qt = quant.quantize_tensor(_rand((32, 24), seed=1), "int8")
    req = registry.request("gemm_wq", x, qt.q, qt.scales)
    impl = registry.select("gemm_wq", req, resolve_backend("interpret"))
    assert impl.name == "pallas" and impl.pass_interpret
    # dense-float "weights" are not a quantized request -> oracle serves it
    wf = _rand((32, 24), seed=1)
    req = registry.request("gemm_wq", x, wf, qt.scales)
    assert registry.select("gemm_wq", req,
                           resolve_backend("interpret")).name == "ref"
    with use_backend("interpret"):
        out = ops.gemm_wq(x, wf, jnp.ones((1, 24), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ wf),
                               rtol=1e-5, atol=1e-5)


def test_dense_layer_dispatches_quantized():
    """layers.dense with a QuantTensor routes gemm_wq on every backend."""
    from repro.models.layers import dense

    x = _rand((2, 10, 32))
    w = _rand((32, 24), seed=1)
    qt = quant.quantize_tensor(w, "int8", block=8)
    want = x @ qt.dequantize()
    got_xla = dense(x, qt)
    with use_backend("interpret"):
        got_kernel = dense(x, qt, act=None)
    np.testing.assert_allclose(np.asarray(got_xla), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_kernel), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# quantized paged attention
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_paged_attention_quantized_parity(dtype):
    B, K, G, D, N, page, P = 3, 2, 4, 16, 9, 8, 4
    q = _rand((B, K, G, D), seed=4, scale=0.5)
    kp = _rand((N, page, K, D), seed=5, scale=0.5)
    vp = _rand((N, page, K, D), seed=6)
    tables = jax.random.randint(KEY, (B, P), 0, N, jnp.int32)
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    # one layer's pools: the op's leading-1 stacked layout, layer 0
    kq, ks = quant.quantize_kv(kp[None], dtype)
    vq, vs = quant.quantize_kv(vp[None], dtype)
    with use_backend("ref"):
        want = ops.paged_attention(q, kq, vq, tables, lengths, 0, ks, vs)
    with use_backend("interpret"):
        got = ops.paged_attention(q, kq, vq, tables, lengths, 0, ks, vs)
    req = registry.request("paged_attention", q, kq, vq, tables, lengths,
                           jnp.int32(0), ks, vs)
    assert registry.select("paged_attention", req,
                           resolve_backend("interpret")).name == "pallas"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-4, atol=5e-4)
    # and the quantized read stays close to the unquantized pools
    dense_out = ops.paged_attention(q, kp[None], vp[None], tables, lengths,
                                    0)
    tol = 0.05 if dtype == "int8" else 0.2
    np.testing.assert_allclose(np.asarray(want), np.asarray(dense_out),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", ["int8", "fp8"])
def test_paged_attention_no_float_page_bounce(dtype):
    """The quantized paged-attention kernel contracts QK^T and PV directly
    against the storage codes (native low-precision dot_general), folding
    the per-row scales into the (G, page) scores — it must never
    materialize a float page-sized (page, D) dequantized copy in-kernel."""
    B, K, G, D, N, page, P = 2, 2, 4, 32, 5, 8, 3
    q = _rand((B, K, G, D), scale=0.5)
    kq, ks = quant.quantize_kv(_rand((1, N, page, K, D), seed=1), dtype)
    vq, vs = quant.quantize_kv(_rand((1, N, page, K, D), seed=2), dtype)
    tables = jax.random.randint(KEY, (B, P), 0, N, jnp.int32)
    lengths = jnp.asarray([5, 20], jnp.int32)

    with use_backend("interpret"):
        jaxpr = jax.make_jaxpr(
            lambda *a: ops.paged_attention(*a))(q, kq, vq, tables, lengths,
                                                0, ks, vs)

    bad = []

    def walk(jx):
        for eqn in jx.eqns:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is None or not hasattr(aval, "shape"):
                    continue
                if (aval.dtype in (jnp.float32, jnp.bfloat16)
                        and tuple(aval.shape[-2:]) == (page, D)):
                    bad.append((eqn.primitive.name, aval.str_short()))
            for p in eqn.params.values():
                inner = getattr(p, "jaxpr", p)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jaxpr.jaxpr)
    assert not bad, f"float page-sized intermediates in kernel: {bad}"


def test_quantized_pools_without_scales_error_loudly():
    """int8 pools WITHOUT scale operands must neither select the kernel nor
    silently run attention over raw codes — the public op refuses."""
    B, K, G, D, N, page, P = 2, 2, 2, 16, 5, 4, 3
    q = _rand((B, K, G, D))
    kq = jnp.zeros((1, N, page, K, D), jnp.int8)
    tables = jnp.zeros((B, P), jnp.int32)
    lengths = jnp.asarray([3, 4], jnp.int32)
    req = registry.request("paged_attention", q, kq, kq, tables, lengths,
                           jnp.int32(0))
    assert registry.select("paged_attention", req,
                           resolve_backend("interpret")).name == "ref"
    with pytest.raises(ValueError, match="k_scale"):
        ops.paged_attention(q, kq, kq, tables, lengths, 0)


# --------------------------------------------------------------------------
# quantize_params + model forward
# --------------------------------------------------------------------------
def test_quantize_params_selection_and_bytes():
    cfg = _cfg(param_dtype="bfloat16", weight_dtype="int8", quant_block=32)
    params = init(jax.random.PRNGKey(0), cfg)
    qp = quant.quantize_params(params, cfg)
    assert quant.is_quantized(qp) and not quant.is_quantized(params)
    # matmul weights wrapped, embed per-row, norms untouched
    assert isinstance(qp["blocks"][0]["attn"]["q_proj"]["kernel"],
                      QuantTensor)
    assert isinstance(qp["blocks"][0]["mlp"]["up"]["kernel"], QuantTensor)
    assert isinstance(qp["embed"]["table"], QuantTensor)
    assert qp["embed"]["table"].axis == -1
    assert not isinstance(qp["final_norm"]["scale"], QuantTensor)
    ratio = quant.param_bytes(qp) / quant.param_bytes(params)
    assert ratio <= 0.55, ratio
    # idempotent
    again = quant.quantize_params(qp, cfg)
    assert quant.param_bytes(again) == quant.param_bytes(qp)


def test_quantize_params_skips_router_and_conv():
    moe_cfg = reduced(get_arch("qwen2-moe-a2.7b")).replace(
        dtype="float32", param_dtype="float32", weight_dtype="int8")
    params = init(jax.random.PRNGKey(0), moe_cfg)
    qp = quant.quantize_params(params, moe_cfg)
    block = qp["blocks"][0]
    assert not isinstance(block["moe"]["router"]["kernel"], QuantTensor)
    assert isinstance(block["moe"]["experts"]["gate"], QuantTensor)
    rec_cfg = reduced(get_arch("recurrentgemma-2b")).replace(
        dtype="float32", param_dtype="float32", weight_dtype="int8")
    rp = quant.quantize_params(init(jax.random.PRNGKey(0), rec_cfg), rec_cfg)
    leaves = jax.tree_util.tree_flatten_with_path(
        rp, is_leaf=lambda x: isinstance(x, QuantTensor))[0]
    for path, leaf in leaves:
        keys = [str(getattr(k, "key", "")) for k in path]
        if "conv" in keys:
            assert not isinstance(leaf, QuantTensor), keys


def test_quantized_forward_close_and_moe_kernel_scope():
    cfg = reduced(get_arch("qwen2-moe-a2.7b")).replace(
        dtype="float32", param_dtype="float32", weight_dtype="int8",
        quant_block=16)
    params = init(jax.random.PRNGKey(0), cfg)
    qp = quant.quantize_params(params, cfg)
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 12)), jnp.int32)
    want, _, _ = forward(params, cfg, toks)
    got, _, _ = forward(qp, cfg, toks)
    # weight-only quantization: close, not equal
    w, g = np.asarray(want), np.asarray(got)
    rel = np.linalg.norm(g - w) / np.linalg.norm(w)
    assert rel < 0.05, rel
    assert np.abs(g - w).max() < 0.25 * np.abs(w).max()
    # the quantized expert FFN under a kernel scope (per-expert gemm_wq
    # grouped GEMM) matches the astype-dequant XLA path
    with use_backend("interpret"):
        got_k, _, _ = forward(qp, cfg, toks)
    np.testing.assert_allclose(np.asarray(got_k), np.asarray(got),
                               rtol=2e-3, atol=2e-3)


def test_quant_teacher_forced_token_match():
    """Per-position greedy agreement of the int8 model vs fp32 baseline."""
    cfg = _cfg(weight_dtype="int8", quant_block=32)
    params = init(jax.random.PRNGKey(0), cfg)
    qp = quant.quantize_params(params, cfg)
    rng = np.random.default_rng(0)
    match = total = 0
    for _ in range(4):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 32)),
                           jnp.int32)
        hb, _, _ = forward(params, cfg, toks)
        hq, _, _ = forward(qp, cfg, toks)
        gb = np.asarray(jnp.argmax(
            logits_fn(params, cfg, hb)[0, :, :cfg.vocab_size], -1))
        gq = np.asarray(jnp.argmax(
            logits_fn(qp, cfg, hq)[0, :, :cfg.vocab_size], -1))
        match += int((gb == gq).sum())
        total += len(gb)
    # random-init logits are nearly tied, so this floor is conservative;
    # benchmarks/quant_accuracy.py asserts >= 0.95 on a trained model
    assert match / total >= 0.85, (match, total)


# --------------------------------------------------------------------------
# engine integration: quantized KV + weights
# --------------------------------------------------------------------------
def _mixed_requests(cfg, n, seed, lo=4, hi=18, new_lo=3, new_hi=8):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        rng.integers(lo, hi)).astype(np.int32),
                    max_new_tokens=int(rng.integers(new_lo, new_hi)))
            for i in range(n)]


def test_engine_quantized_kv_matches_dense_greedy():
    """int8 paged KV alone (dense weights) preserves greedy decode on the
    overwhelming majority of tokens across interleaved admits/finishes."""
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    reqs = _mixed_requests(cfg, 6, seed=7)
    outs = {}
    for kv in ("", "int8"):
        engine = ServeEngine(cfg.replace(kv_dtype=kv), params, max_slots=3,
                             max_len=64, paged=True, page_size=8,
                             prefill_chunk=6)
        res = engine.run([Request(uid=r.uid, prompt=r.prompt,
                                  max_new_tokens=r.max_new_tokens)
                          for r in reqs])
        assert all(r.finish_reason == "length" for r in res)
        outs[kv] = [r.tokens for r in res]
    match = sum(int(x == y) for a, b in zip(outs[""], outs["int8"])
                for x, y in zip(a, b))
    total = sum(len(a) for a in outs[""])
    assert match / total >= 0.9, (match, total)


def test_engine_quantized_cache_layout_and_bytes():
    cfg = _cfg(kv_dtype="int8", weight_dtype="int8")
    params = init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=32, paged=True,
                         page_size=8)
    assert quant.is_quantized(engine.params)
    leaves = {"".join(str(k) for k in p): l
              for p, l in jax.tree_util.tree_flatten_with_path(
                  engine.cache)[0]}
    k_pools = [l for p, l in leaves.items()
               if p.endswith("['self']['k']")
               and engine.n_blocks in l.shape[:2]]
    scales = [l for p, l in leaves.items() if "k_scale" in p]
    assert k_pools and all(l.dtype == jnp.int8 for l in k_pools)
    assert scales and all(l.dtype == jnp.float16 for l in scales)
    # sizing reflects the narrow dtype: quantized pool < 0.55x the fp32 pool
    dense_cache = init_cache(cfg.replace(kv_dtype=""), 2, 32,
                             n_blocks=engine.n_blocks, page_size=8)
    ratio = (kv_bytes(engine.cache, pool_n_blocks=engine.n_blocks)
             / kv_bytes(dense_cache, pool_n_blocks=engine.n_blocks))
    assert ratio <= 0.55 / 2, ratio   # int8+f16 scales vs fp32 ~ 0.27


def test_kv_dtype_requires_paged_and_no_encdec():
    cfg = _cfg(kv_dtype="int8")
    params = init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(cfg, params, max_slots=1, max_len=32, paged=False)
    vlm = _cfg(kv_dtype="int8")
    engine = ServeEngine(vlm, params, max_slots=1, max_len=32, paged=True,
                         page_size=8)
    bad = Request(uid=0, prompt=np.arange(4, dtype=np.int32),
                  max_new_tokens=2,
                  extra_embeds=np.zeros((2, vlm.d_model), np.float32))
    [res] = engine.run([bad])
    assert res.finish_reason == "rejected"
    assert "chunked-prefill" in res.detail


@pytest.mark.parametrize("kv", ["int8", "fp8"])
def test_prefix_shared_quantized_blocks_roundtrip(kv):
    """Prefix-cache block sharing over quantized pools: the per-row scale
    tensors ride along on share and copy-on-write, so a warm cache (hits +
    a full-match COW) emits exactly the tokens the cold quantized engine
    does — a dropped scale would skew every dequantized prefix row."""
    cfg = _cfg(kv_dtype=kv)
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(60)
    sys_prompt = rng.integers(0, 256, 16).astype(np.int32)   # 2 full pages
    reqs = lambda: ([Request(uid=0, prompt=sys_prompt.copy(),  # full match
                             max_new_tokens=4)]
                    + [Request(uid=i, prompt=np.concatenate(
                           [sys_prompt,
                            rng2.integers(0, 256, 5).astype(np.int32)]),
                           max_new_tokens=4)
                       for i, rng2 in
                       enumerate(np.random.default_rng(61).spawn(2), 1)])
    outs = {}
    for pc in (False, True):
        engine = ServeEngine(cfg, params, max_slots=2, max_len=64,
                             paged=True, page_size=8, prefill_chunk=8,
                             prefix_cache=pc)
        engine.run([Request(uid=99, prompt=sys_prompt, max_new_tokens=2)])
        res = engine.run(reqs())
        outs[pc] = [r.tokens for r in res]
        if pc:
            assert engine.stats["prefix_hits"] == 3
            assert engine.stats["prefix_cow"] == 1   # the full-match resubmit
            assert engine.allocator.n_live == 0
    assert outs[True] == outs[False], \
        f"{kv} scales did not survive share/COW"


def test_copy_block_carries_quant_scales():
    """cache.copy_block duplicates K/V *and* the per-row scale leaves of a
    quantized pool (and leaves non-pool state untouched)."""
    from repro.models.cache import copy_block

    cfg = _cfg(kv_dtype="int8")
    cache = init_cache(cfg, 2, 32, n_blocks=6, page_size=8)

    def fill(leaf):
        if leaf.dtype == jnp.int8:
            return jnp.arange(leaf.size, dtype=jnp.int32).reshape(
                leaf.shape).astype(jnp.int8)
        return jnp.arange(leaf.size, dtype=jnp.float32).reshape(
            leaf.shape).astype(leaf.dtype)

    cache = jax.tree.map(fill, cache)
    out = copy_block(cache, 2, 4, 6)

    def check(path, a, b):
        keys = [getattr(k, "key", None) for k in path]
        axis = 1 if "blocks" in keys else 0
        if "self" in keys and a.shape[axis] == 6:
            src = jnp.take(a, 2, axis)
            dst = jnp.take(b, 4, axis)
            np.testing.assert_array_equal(np.asarray(src), np.asarray(dst))
            # untouched blocks keep their contents
            np.testing.assert_array_equal(np.asarray(jnp.take(a, 1, axis)),
                                          np.asarray(jnp.take(b, 1, axis)))
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    jax.tree_util.tree_map_with_path(check, cache, out)
    # the quantized pool really has scale leaves, and they were copied
    leaves = jax.tree_util.tree_flatten_with_path(out)[0]
    scale_leaves = [l for p, l in leaves
                    if any(getattr(k, "key", None) == "k_scale" for k in p)]
    assert scale_leaves, "quantized pool must carry k_scale leaves"


def test_rejection_detail_reports_budget():
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_slots=1, max_len=16)
    [res] = engine.run([Request(uid=0, prompt=np.zeros(14, np.int32),
                                max_new_tokens=8)])
    assert res.finish_reason == "rejected"
    assert "22 tokens > 16" in res.detail
    paged = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                        page_size=8, max_blocks=5)
    [res] = paged.run([Request(uid=1, prompt=np.zeros(40, np.int32),
                               max_new_tokens=8)])
    assert res.finish_reason == "rejected"
    assert "blocks" in res.detail and "KV bytes" in res.detail
    assert str(4 * paged._block_kv_bytes) in res.detail  # capacity budget


def test_n_blocks_for_bytes_doubles_at_int8():
    cfg = _cfg(dtype="bfloat16")
    qcfg = cfg.replace(kv_dtype="int8")
    budget = 1 << 20
    n_bf16 = n_blocks_for_bytes(cfg, budget, 8)
    n_int8 = n_blocks_for_bytes(qcfg, budget, 8)
    assert 1.8 * n_bf16 <= n_int8 <= 2.0 * n_bf16
    assert kv_block_bytes(qcfg, 8) < 0.55 * kv_block_bytes(cfg, 8)
    # the engine's budget-driven pool sizing flows through the helper
    params = init(jax.random.PRNGKey(0), cfg.replace(dtype="float32"))
    small = ServeEngine(cfg.replace(dtype="float32"), params, max_slots=2,
                        max_len=64, paged=True, page_size=8,
                        kv_budget_bytes=kv_block_bytes(
                            cfg.replace(dtype="float32"), 8) * 3)
    assert small.allocator.capacity == 3


# --------------------------------------------------------------------------
# checkpoint round-trip
# --------------------------------------------------------------------------
def test_ckpt_roundtrip_quantized_params(tmp_path):
    from repro.ckpt import restore_checkpoint, save_checkpoint

    cfg = _cfg(weight_dtype="int8", quant_block=32)
    params = quant.quantize_params(init(jax.random.PRNGKey(0), cfg), cfg)
    save_checkpoint(tmp_path, 1, params)
    template = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                            params)
    restored, _ = restore_checkpoint(tmp_path, template)
    qt = restored["blocks"][0]["attn"]["q_proj"]["kernel"]
    assert isinstance(qt, QuantTensor)
    assert qt.q.dtype == jnp.int8 and qt.scales.dtype == jnp.float16
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


# --------------------------------------------------------------------------
# roofline / memfloor byte terms
# --------------------------------------------------------------------------
def test_memfloor_decode_bytes_follow_quant_dtypes():
    from repro.configs import ShapeConfig
    from repro.core.memfloor import MeshSizes, hbm_bytes_floor
    from repro.core.roofline import traffic_dtype_bytes

    assert traffic_dtype_bytes("int8") == 1
    assert traffic_dtype_bytes("fp8") == 1
    assert traffic_dtype_bytes("", 2.0) == 2.0
    cfg = get_arch("qwen3-0.6b")
    shape = ShapeConfig(name="d", kind="decode", seq_len=2048, global_batch=8)
    mesh = MeshSizes(n_data=1, n_model=1)
    base = hbm_bytes_floor(cfg, shape, mesh, fsdp=False)
    q = hbm_bytes_floor(
        cfg.replace(weight_dtype="int8", kv_dtype="int8"), shape, mesh,
        fsdp=False)
    assert q["weights"] == pytest.approx(base["weights"] / 2)
    assert q["cache"] < 0.55 * base["cache"]
    assert q["total"] < base["total"]
