"""Attention layer: chunked flash vs naive oracle, GQA layouts, decode paths,
RoPE/qk-norm invariants."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.kernels import ref
from repro.models.attention import flash_attention_xla
from repro.models.layers import rope, softcap


def _naive(q, k, v, *, causal, window, cap, scale):
    """(B, Sq, K, G, D) vs (B, Skv, K, D) oracle via the kernel ref."""
    B, Sq, K, G, D = q.shape
    Skv = k.shape[1]
    qf = q.transpose(0, 2, 3, 1, 4).reshape(B * K * G, Sq, D)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3).reshape(B * K, Skv, D), G, 0)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3).reshape(B * K, Skv, D), G, 0)
    out = ref.flash_attention_ref(qf, kf, vf, causal=causal, window=window,
                                  cap=cap, scale=scale)
    return out.reshape(B, K, G, Sq, D).transpose(0, 3, 1, 2, 4)


@pytest.mark.parametrize("q_chunk", [8, 32, 1024])
@pytest.mark.parametrize("window,cap", [(0, 0.0), (16, 0.0), (0, 30.0)])
def test_flash_xla_chunks(q_chunk, window, cap):
    B, S, K, G, D = 2, 48, 2, 2, 16
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, S, K, G, D)) * 0.5
    k = jax.random.normal(kk, (B, S, K, D)) * 0.5
    v = jax.random.normal(kv, (B, S, K, D))
    got = flash_attention_xla(q, k, v, causal=True, window=window, cap=cap,
                              scale=0.25, q_chunk=q_chunk, kv_chunk=q_chunk)
    want = _naive(q, k, v, causal=True, window=window, cap=cap, scale=0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_flash_xla_ragged_kv():
    """kv_lens masks trailing positions per batch row."""
    B, S, K, G, D = 2, 32, 1, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, 1, K, G, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    lens = jnp.asarray([10, 32])
    got = flash_attention_xla(q, k, v, causal=False, window=0, cap=0.0,
                              scale=0.35, q_chunk=1, kv_chunk=8, kv_lens=lens)
    # row 0 must equal attention over first 10 kv only
    want0 = _naive(q[:1], k[:1, :10], v[:1, :10], causal=False, window=0,
                   cap=0.0, scale=0.35)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want0[0]),
                               rtol=2e-3, atol=2e-3)


def test_flash_xla_q_offset():
    """Chunked prefill: q_offset shifts causal masking."""
    B, K, G, D = 1, 1, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    S = 24
    q = jax.random.normal(ks[0], (B, S, K, G, D))
    k = jax.random.normal(ks[1], (B, S, K, D))
    v = jax.random.normal(ks[2], (B, S, K, D))
    full = flash_attention_xla(q, k, v, causal=True, window=0, cap=0.0,
                               scale=0.35, q_chunk=8, kv_chunk=8)
    # second half queries with q_offset = 12 against the full KV
    half = flash_attention_xla(q[:, 12:], k, v, causal=True, window=0,
                               cap=0.0, scale=0.35, q_chunk=4, kv_chunk=8,
                               q_offset=12)
    np.testing.assert_allclose(np.asarray(half), np.asarray(full[:, 12:]),
                               rtol=2e-3, atol=2e-3)


def test_rope_rotation_invariant():
    """RoPE preserves norms and relative positions: <q_m, k_n> depends only
    on m - n."""
    D = 16
    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (1, 1, 1, D))
    pos_a = jnp.asarray([[5]])
    pos_b = jnp.asarray([[9]])
    ra = rope(jnp.broadcast_to(x, (1, 1, 1, D)), pos_a, 10000.0)
    rb = rope(jnp.broadcast_to(x, (1, 1, 1, D)), pos_b, 10000.0)
    np.testing.assert_allclose(float(jnp.linalg.norm(ra)),
                               float(jnp.linalg.norm(x)), rtol=1e-5)
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, D))
    # shift both positions by +7: inner product unchanged
    q1 = rope(x, jnp.asarray([[3]]), 1e4)
    k1 = rope(y, jnp.asarray([[1]]), 1e4)
    q2 = rope(x, jnp.asarray([[10]]), 1e4)
    k2 = rope(y, jnp.asarray([[8]]), 1e4)
    np.testing.assert_allclose(float((q1 * k1).sum()), float((q2 * k2).sum()),
                               rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(st.floats(1.0, 100.0))
def test_softcap_bounds(cap):
    x = jnp.linspace(-1000, 1000, 101)
    y = softcap(x, cap)
    assert float(jnp.abs(y).max()) <= cap * 1.0001
    # identity for cap=0
    np.testing.assert_array_equal(np.asarray(softcap(x, 0.0)), np.asarray(x))


def test_softcap_monotone():
    x = jnp.linspace(-50, 50, 201)
    y = softcap(x, 30.0)
    assert bool((jnp.diff(y) > 0).all())


def test_pallas_attention_impl_matches_xla():
    """cfg.kernel_backend='interpret' routes the model through the Pallas
    kernel (interpret mode) and must equal the XLA flash path."""
    import numpy as np
    from repro.configs import get_arch, reduced
    from repro.models import forward, init

    cfg = reduced(get_arch("gemma2-27b")).replace(dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
    h_xla, _, _ = forward(params, cfg.replace(kernel_backend=""), toks)
    h_pl, _, _ = forward(params, cfg.replace(kernel_backend="interpret"),
                         toks)
    np.testing.assert_allclose(np.asarray(h_xla), np.asarray(h_pl),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
@pytest.mark.parametrize("shape", ["chunk", "verify"])
def test_paged_extend_matches_token_by_token_decode(shape, kv_dtype):
    """``attention_paged_extend`` scores T new tokens as T single-token
    ``attention_decode`` steps on the paged pool would, and writes the same
    rows: a chunk of one slot through its table row with a ragged tail, and
    verification of three slots (different ``pos``/``n_valid``, one
    inactive, one running past its table)."""
    from repro.configs import get_arch, reduced
    from repro.models.attention import (attention_decode, attention_init,
                                        attention_paged_extend)
    from repro.quant import quantize_kv

    cfg = reduced(get_arch("qwen3-0.6b")).replace(
        d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, dtype="float32",
        kv_dtype=kv_dtype)
    K, hd, N, page, P, T = 2, 32, 14, 4, 4, 4
    p = attention_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    tables = jnp.arange(1, 13, dtype=jnp.int32).reshape(3, P)
    kk, kv, kx = jax.random.split(jax.random.PRNGKey(1), 3)
    pool = {"k": jax.random.normal(kk, (N, page, K, hd)),
            "v": jax.random.normal(kv, (N, page, K, hd))}
    if kv_dtype:
        for n in ("k", "v"):
            pool[n], pool[n + "_scale"] = quantize_kv(pool[n], kv_dtype)
    if shape == "chunk":
        tables = jax.lax.dynamic_slice(tables, (1, 0), (1, P))
        pos, n_valid, active = np.array([5]), np.array([3]), None
    else:
        pos, n_valid = np.array([3, 9, 13]), np.array([3, 2, 4])
        active = np.array([True, False, True])
    B = tables.shape[0]
    x = jax.random.normal(kx, (B, T, cfg.d_model))
    t = np.arange(T)
    valid = (t[None] < n_valid[:, None]) & (pos[:, None] + t < P * page)
    if active is not None:
        valid &= active[:, None]
    out, got = attention_paged_extend(
        p, cfg, x, pool, pos=jnp.asarray(pos, jnp.int32),
        n_valid=jnp.asarray(n_valid, jnp.int32),
        active=None if active is None else jnp.asarray(active),
        block_tables=tables, compute_dtype=jnp.float32)

    want, outs = pool, []
    for i in range(T):
        o, want = attention_decode(
            p, cfg, x[:, i:i + 1], want, is_local=False,
            pos=jnp.asarray(pos + i, jnp.int32), compute_dtype=jnp.float32,
            active=jnp.asarray(valid[:, i]), block_tables=tables)
        outs.append(o)
    tol = 5e-4 if kv_dtype else 1e-5
    np.testing.assert_allclose(
        np.asarray(out)[valid], np.asarray(jnp.concatenate(outs, 1))[valid],
        rtol=tol, atol=tol)

    # the pool: the valid rows as decode wrote them, every other row as it
    # was (padding, the inactive slot, positions past the table)
    written = np.zeros((N, page), bool)
    for b, i in zip(*np.nonzero(valid)):
        q = pos[b] + i
        written[np.asarray(tables)[b, q // page], q % page] = True
    assert written.sum() == valid.sum()
    for n in pool:
        np.testing.assert_array_equal(np.asarray(got[n])[written],
                                      np.asarray(want[n])[written])
        np.testing.assert_array_equal(np.asarray(got[n])[~written],
                                      np.asarray(pool[n])[~written])
