"""Backend-registry dispatch: parity across every registered op and dtype,
capability negotiation (unsupported requests fall to ref, never error),
``use_backend`` scoping, block-size tuning, and kernel-backend
validation."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, StrategyConfig
from repro.kernels import ops
from repro.kernels.dispatch import (BACKENDS, blocks_from_pairs,
                                    default_backend_name, registry,
                                    requested_backend, resolve_backend,
                                    use_backend)

KEY = jax.random.PRNGKey(0)


def _rand(shape, dtype=jnp.float32, seed=0, scale=1.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * scale
    return x.astype(dtype)


def _op_calls(dtype):
    """One canonical invocation per registered op (thunks)."""
    from repro.quant import quantize_tensor

    x = _rand((48, 40), dtype)
    w = _rand((40, 56), dtype, seed=1)
    wq = quantize_tensor(_rand((40, 56), jnp.float32, seed=1), "int8",
                         block=20)
    q = _rand((4, 48, 16), dtype, scale=0.5)
    k = _rand((2, 48, 16), dtype, seed=1, scale=0.5)
    v = _rand((2, 48, 16), dtype, seed=2)
    a = jax.random.uniform(KEY, (2, 40, 24), minval=0.5,
                           maxval=0.99).astype(dtype)
    b = _rand((2, 40, 24), dtype, seed=3)
    table = _rand((64, 32), dtype)
    idx = jax.random.randint(KEY, (37,), 0, 64)
    qd = _rand((3, 2, 4, 16), dtype, seed=4, scale=0.5)   # (B, K, G, D)
    pool_k = _rand((2, 9, 8, 2, 16), dtype, seed=5, scale=0.5)  # 2 layers
    pool_v = _rand((2, 9, 8, 2, 16), dtype, seed=6)
    tables = jax.random.randint(KEY, (3, 4), 0, 9, jnp.int32)
    lengths = jnp.asarray([5, 17, 30], jnp.int32)
    # latent attention: 4 heads over 256-column rows, 128 value columns
    q_lat = _rand((3, 4, 256), dtype, seed=7, scale=0.1)
    pool_lat = _rand((2, 9, 8, 256), dtype, seed=8)
    from repro.kernels.gemm_sparse import block_mask_from_weight
    mask = block_mask_from_weight(w.astype(jnp.float32), 8, 8, 0.5)
    return {
        "gemm": lambda: ops.gemm(x, w, scale=0.5, act="gelu"),
        "gemm_wq": lambda: ops.gemm_wq(x, wq.q, wq.scales, scale=0.5,
                                       act="gelu"),
        "gemm_sparse": lambda: ops.gemm_sparse(x, w, mask, scale=0.5,
                                               act="gelu"),
        "flash_attention": lambda: ops.flash_attention(q, k, v, causal=True),
        "lru_scan": lambda: ops.lru_scan(a, b),
        "gather_rows": lambda: ops.gather_rows(table, idx),
        "packed_gather_rows": lambda: ops.packed_gather_rows(table, idx),
        "instream_scale_reduce": lambda: ops.instream_scale_reduce(
            x, scale=2.0, shift=-0.5),
        "paged_attention": lambda: ops.paged_attention(
            qd, pool_k, pool_v, tables, lengths, 1, cap=30.0),
        "paged_mla": lambda: ops.paged_mla(q_lat, pool_lat, tables, lengths,
                                           1, dv=128),
    }


# --------------------------------------------------------------------------
# parity: pallas_interpret vs ref across every registered op and dtype
# --------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("op", sorted(
    ["gemm", "gemm_wq", "gemm_sparse", "flash_attention", "lru_scan",
     "gather_rows", "packed_gather_rows", "instream_scale_reduce",
     "paged_attention"]))
def test_registry_parity_interpret_vs_ref(op, dtype):
    calls = _op_calls(dtype)
    with use_backend("ref"):
        want = calls[op]()
    with use_backend("interpret"):
        got = calls[op]()
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=tol, atol=tol)


def test_every_op_is_registered():
    assert registry.ops() == sorted(_op_calls(jnp.float32))
    for op in registry.ops():
        impls = registry.implementations(op)
        # each op has a kernel entry and a universal ref fallback
        assert any("ref" in e.backends for e in impls), op
        assert any(e.pass_interpret for e in impls), op


# --------------------------------------------------------------------------
# capability negotiation: unsupported requests fall to ref, never error
# --------------------------------------------------------------------------
def test_negotiates_down_tiny_head_dim():
    """D=4 is below the kernel's sublane floor -> ref oracle, same answer."""
    q = _rand((4, 32, 4), scale=0.5)
    k = _rand((2, 32, 4), seed=1, scale=0.5)
    v = _rand((2, 32, 4), seed=2)
    with use_backend("ref"):
        want = ops.flash_attention(q, k, v, causal=True)
    with use_backend("interpret"):
        got = ops.flash_attention(q, k, v, causal=True)  # must not error
    req = registry.request("flash_attention", q, k, v)
    impl = registry.select("flash_attention", req, resolve_backend("interpret"))
    assert impl.name == "ref"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_negotiates_down_integer_dtype():
    x = jnp.arange(12, dtype=jnp.int32).reshape(3, 4)
    w = jnp.ones((4, 5), jnp.int32)
    with use_backend("interpret"):
        got = ops.gemm(x, w)  # int gemm: kernel declines, oracle serves
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(x, np.float32) @ np.ones((4, 5)),
                               rtol=1e-6, atol=1e-6)


def test_supported_request_selects_kernel():
    q = _rand((4, 32, 16))
    k = _rand((2, 32, 16), seed=1)
    req = registry.request("flash_attention", q, k, k)
    impl = registry.select("flash_attention", req, resolve_backend("interpret"))
    assert impl.name == "pallas" and impl.pass_interpret


def test_pallas_backend_off_tpu_negotiates_down():
    """Pinning 'pallas' on a platform with no compiled kernels must fall to
    the oracle, not crash inside pallas_call."""
    if jax.default_backend() == "tpu":
        pytest.skip("compiled pallas exists here")
    x = _rand((48, 40))
    w = _rand((40, 56), seed=1)
    with use_backend("ref"):
        want = ops.gemm(x, w)
    with use_backend("pallas"):
        got = ops.gemm(x, w)  # must not error
        req = registry.request("gemm", x, w)
        assert registry.select("gemm", req, resolve_backend()).name == "ref"
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_resolution_record_shows_ref_off_tpu():
    """The registry records which impl each dispatch chose: pinning
    'pallas' on CPU runs the oracle, and the record says so."""
    if jax.default_backend() == "tpu":
        pytest.skip("compiled pallas exists here")
    x = _rand((48, 40))
    w = _rand((40, 56), seed=1)
    registry.clear_resolutions()
    with use_backend("pallas"):
        ops.gemm(x, w)
        ops.gemm(x, w)
    assert registry.resolutions() == {("gemm", "ref"): 2}
    with use_backend("interpret"):
        ops.gemm(x, w)
    assert registry.resolutions()[("gemm", "pallas")] == 1
    registry.clear_resolutions()
    assert registry.resolutions() == {}


def test_auto_scope_selects_kernels_only_on_tpu(monkeypatch):
    """A ``use_backend("auto")`` scope opens the model's kernel path where
    the platform resolves auto to the Pallas kernels, and nowhere else."""
    from repro.kernels import dispatch

    with use_backend("auto"):
        assert not dispatch.kernel_scope_active()
        monkeypatch.setattr(dispatch.jax, "default_backend", lambda: "tpu")
        assert dispatch.kernel_scope_active()


def test_resolution_record_counts_at_trace_time():
    """Inside jit the record counts traces, not executions."""
    x = _rand((16, 16))
    registry.clear_resolutions()
    f = jax.jit(lambda a: ops.gemm(a, a))
    with use_backend("ref"):
        f(x)
        f(x)
    assert registry.resolutions() == {("gemm", "ref"): 1}
    registry.clear_resolutions()


def test_packed_gather_declines_on_tpu():
    """The TPU compiler refuses the packed-gather kernel (dynamic one-row
    reads of a bf16 window; a whole-table VMEM window), so a TPU request
    negotiates down to the oracle while CPU interpret keeps the kernel."""
    from repro.kernels.dispatch import Backend, OpRequest

    shapes, dtypes = ((4096, 1024), (64,)), ("bfloat16", "int32")
    tpu = OpRequest("packed_gather_rows", shapes, dtypes, "tpu")
    cpu = OpRequest("packed_gather_rows", shapes, dtypes, "cpu")
    assert registry.select("packed_gather_rows", tpu,
                           Backend("pallas", "tpu")).name == "ref"
    assert registry.select("packed_gather_rows", cpu,
                           Backend("interpret", "cpu")).name == "pallas"


def test_unknown_kwargs_raise():
    """Typo'd kwargs fail loudly, as the pre-registry jitted ops did."""
    x = _rand((8, 8))
    w = _rand((8, 8), seed=1)
    with pytest.raises(TypeError, match="blok_m"):
        ops.gemm(x, w, blok_m=64)
    q = _rand((2, 16, 16))
    with pytest.raises(TypeError, match="block"):
        ops.flash_attention(q, q, q, block=16)


# --------------------------------------------------------------------------
# use_backend scoping
# --------------------------------------------------------------------------
def test_use_backend_round_trips():
    assert requested_backend() is None
    with use_backend("interpret") as be:
        assert be.name == "interpret" and be.interpret
        assert requested_backend() == "interpret"
        with use_backend("ref") as inner:
            assert inner.name == "ref"
            assert requested_backend() == "ref"
        assert requested_backend() == "interpret"
    assert requested_backend() is None
    assert resolve_backend().name == default_backend_name()


def test_use_backend_restores_on_error():
    with pytest.raises(RuntimeError):
        with use_backend("interpret"):
            raise RuntimeError("boom")
    assert requested_backend() is None


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        with use_backend("cuda"):
            pass
    with pytest.raises(ValueError):
        resolve_backend("triton")
    assert set(BACKENDS) == {"ref", "interpret", "pallas"}


# --------------------------------------------------------------------------
# block-size tuning table
# --------------------------------------------------------------------------
def test_blocks_bucketed_by_shape():
    small = registry.request("gemm", _rand((48, 40)), _rand((40, 56)))
    large = registry.request("gemm", _rand((512, 256)), _rand((256, 512)))
    assert registry.blocks_for("gemm", small)["block_m"] == 32
    assert registry.blocks_for("gemm", large)["block_m"] == 128


def test_block_overrides_scope_and_nest():
    req = registry.request("gemm", _rand((48, 40)), _rand((40, 56)))
    base = registry.blocks_for("gemm", req)
    with use_backend(blocks={"gemm": {"block_m": 8}}):
        assert registry.blocks_for("gemm", req)["block_m"] == 8
        with use_backend(blocks={("gemm", "small"): {"block_m": 16}}):
            assert registry.blocks_for("gemm", req)["block_m"] == 16
    assert registry.blocks_for("gemm", req) == base


def test_block_override_changes_result_not_value():
    x = _rand((100, 96))
    w = _rand((96, 72), seed=1)
    with use_backend("interpret"):
        want = ops.gemm(x, w)
        with use_backend(blocks={"gemm": {"block_m": 64, "block_n": 8,
                                          "block_k": 16}}):
            got = ops.gemm(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_strategy_kernel_blocks_decode():
    sc = StrategyConfig(kernel_blocks=(
        ("gemm", "*", (("block_m", 64),)),
        ("flash_attention", "small", (("block_q", 16),)),
    ))
    blocks = blocks_from_pairs(sc.kernel_blocks)
    assert blocks == {"gemm": {"block_m": 64},
                      ("flash_attention", "small"): {"block_q": 16}}
    req = registry.request("gemm", _rand((48, 40)), _rand((40, 56)))
    with use_backend(blocks=blocks):
        assert registry.blocks_for("gemm", req)["block_m"] == 64


def test_caller_kwargs_beat_tuning_table():
    x = _rand((200, 100))
    w = _rand((100, 150), seed=1)
    with use_backend("interpret"):
        got = ops.gemm(x, w, block_m=64, block_n=64, block_k=64)
    with use_backend("ref"):
        want = ops.gemm(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# model-layer integration
# --------------------------------------------------------------------------
def _tiny_cfg(**kw):
    from repro.configs import get_arch, reduced
    return reduced(get_arch("gemma2-27b")).replace(dtype="float32", **kw)


def test_kernel_backend_is_validated():
    with pytest.raises(ValueError):
        ModelConfig(kernel_backend="cuda")


def test_use_backend_scope_overrides_model_config():
    """A use_backend scope around the model call wins over cfg."""
    from repro.models import forward, init

    cfg = _tiny_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab_size, (1, 16)), jnp.int32)
    want, _, _ = forward(params, cfg.replace(kernel_backend="ref"), toks)
    with use_backend("ref"):
        got, _, _ = forward(params, cfg, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_cfg_backend_routes_whole_graph():
    """cfg.kernel_backend and a use_backend scope are interchangeable: both
    open a whole-graph registry scope (attention AND dense/MLP), so the
    outputs are bit-identical."""
    from repro.models import forward, init

    cfg = _tiny_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (1, 16)), jnp.int32)
    via_cfg, _, _ = forward(params, cfg.replace(kernel_backend="interpret"),
                            toks)
    with use_backend("interpret"):
        via_scope, _, _ = forward(params, cfg, toks)
    np.testing.assert_array_equal(np.asarray(via_cfg), np.asarray(via_scope))
    # and the dense layers really did leave the plain-jnp path
    plain, _, _ = forward(params, cfg, toks)
    assert not np.array_equal(np.asarray(via_cfg), np.asarray(plain))


def test_training_immune_to_ambient_backend(monkeypatch):
    """REPRO_KERNEL_BACKEND (or TPU auto-detection) pins the *default* for
    direct op calls but must never reroute a training graph through the
    forward-only Pallas kernels: grad of an MoE model works under the CI
    env pin."""
    from repro.models import init, lm_loss

    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert default_backend_name() == "interpret"
    from repro.configs import get_arch, reduced
    cfg = reduced(get_arch("deepseek-moe-16b")).replace(dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 8)), jnp.int32)
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(p, cfg, toks, toks))(params)
    assert np.isfinite(float(loss))
    assert all(np.all(np.isfinite(np.asarray(g, np.float32)))
               for g in jax.tree_util.tree_leaves(grads))


def test_spmd_neutralizes_kernel_scope():
    """Under a partitioner, the model entry points neutralize an enclosing
    kernel scope (no pallas_call may trace inside pjit) instead of merely
    skipping it."""
    from repro.kernels.dispatch import kernel_scope_active
    from repro.models.transformer import _model_kernel_scope

    cfg = _tiny_cfg()
    with use_backend("interpret"):
        with _model_kernel_scope(cfg, part=object()):
            assert not kernel_scope_active()
            assert requested_backend() == "ref"
        with _model_kernel_scope(cfg, part=None):
            assert kernel_scope_active()
    assert requested_backend() is None


def test_mlp_dense_registry_parity():
    """apply_mlp under a kernel scope (fused-epilogue gemm) matches jnp."""
    from repro.models.layers import apply_mlp, mlp_init

    p = mlp_init(jax.random.PRNGKey(0), 32, 64, True, jnp.float32)
    x = _rand((2, 10, 32))
    want = apply_mlp(p, x, "silu", True, jnp.float32)
    with use_backend("interpret"):
        got = apply_mlp(p, x, "silu", True, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_recurrent_diag_scan_registry_parity():
    """diag_scan under a kernel scope (carry absorbed into b_1) matches the
    chunked associative-scan path, including a nonzero initial state."""
    from repro.models.recurrent import diag_scan

    a = jax.random.uniform(KEY, (2, 50, 16), minval=0.3, maxval=0.99)
    b = _rand((2, 50, 16), seed=1)
    h0 = _rand((2, 16), seed=2)
    want_h, want_last = diag_scan(a, b, h0, 32)
    with use_backend("interpret"):
        got_h, got_last = diag_scan(a, b, h0, 32)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(want_last),
                               rtol=1e-5, atol=1e-5)


def test_serve_engine_accepts_kernel_backend():
    """Engine pins a backend for its jitted graphs; ref == default output."""
    from repro.models import init
    from repro.serve.engine import Request, ServeEngine

    cfg = _tiny_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(8, dtype=np.int32)

    outs = []
    for backend in (None, "ref"):
        eng = ServeEngine(cfg, params, max_slots=1, max_len=32,
                          kernel_backend=backend)
        res = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=4)])
        outs.append(res[0].tokens)
    assert outs[0] == outs[1]
