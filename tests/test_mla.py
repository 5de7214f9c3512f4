"""Multi-head latent attention (DeepSeek-V2), YaRN positions and drop-free
serving experts, on the reduced DeepSeek-V2-Lite config with seeded
weights, compared on logits.

Tolerances: float32 runs compare paths that compute the same function in
another order (absorbed against decompressed attention, a paged latent
pool against a full forward), so they agree to float32 rounding of logits
of order 1: 2e-4. The kernel against its oracle: float32 to 1e-5 (the
online softmax against one softmax), bfloat16 inputs to 2e-2 (the kernel
rounds the probabilities to bf16 before the value matmul).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import decode_step, extend_step, forward, init, logits_fn
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models.cache import init_cache, kv_block_bytes, latent_width
from repro.models.layers import rope_inv_freq, yarn_mscale
from repro.serve import Request, ServeEngine

ATOL = 2e-4


def _cfg(**kw):
    """Reduced DeepSeek-V2-Lite in float32; an expert capacity wide enough
    that the full forward (which may drop at capacity) drops nothing."""
    cfg = reduced(get_arch("deepseek-v2-lite")).replace(dtype="float32",
                                                        **kw)
    return cfg.replace(moe=dataclasses.replace(cfg.moe,
                                               capacity_factor=64.0))


def _forward_logits(params, cfg, toks):
    h, _, _ = forward(params, cfg, toks)
    return logits_fn(params, cfg, h)[..., :cfg.vocab_size]


def test_reduced_config_is_latent_attention_with_experts():
    cfg = reduced(get_arch("deepseek-v2-lite"))
    assert [s.mixer for s in cfg.all_layers()] == ["mla"] * 3
    assert [s.mlp for s in cfg.all_layers()] == ["dense", "moe", "moe"]
    assert cfg.rope_scaling.factor == 40.0 and cfg.mla.q_lora_rank == 0
    assert latent_width(cfg) == 128          # 64 + 16, padded to a lane tile
    # one block of the latent pool: 3 layers x page rows x 128 columns
    assert kv_block_bytes(cfg, 4) == 3 * 4 * 128 * 2


def test_absorbed_decode_matches_decompressed():
    """One absorbed decode token against a dense latent cache equals the
    last position of the decompressed full-sequence attention."""
    cfg = _cfg()
    p = attn_mod.mla_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    S = 21
    x = jax.random.normal(jax.random.PRNGKey(2), (2, S, cfg.d_model))
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    W = latent_width(cfg)
    want, rows = attn_mod.mla_forward(p, cfg, x, positions=pos,
                                      compute_dtype=jnp.float32, width=W)
    lat = jnp.zeros((2, 32, W)).at[:, :S - 1].set(rows[:, :S - 1])
    got, new = attn_mod.mla_decode(p, cfg, x[:, S - 1:], {"lat": lat},
                                   pos=jnp.int32(S - 1),
                                   compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(got[:, 0]),
                               np.asarray(want[:, -1]), atol=ATOL, rtol=0)
    # the decode wrote the same latent row the full forward made
    np.testing.assert_allclose(np.asarray(new["lat"][:, S - 1]),
                               np.asarray(rows[:, S - 1]), atol=1e-5)


@pytest.mark.parametrize("key_block", [2048, 8],
                         ids=["one-key-block", "key-blocks-of-8"])
def test_paged_prefill_then_decode_matches_full_forward(key_block,
                                                        monkeypatch):
    """Chunked prefill (extend_step) and decode steps through the paged
    latent pool, the pools carried stacked through the layer scan, give the
    logits of one full forward over the same tokens; with key blocks of 8
    rows, each chunk folds in only the blocks up to its last position."""
    monkeypatch.setattr(attn_mod, "EXTEND_KEY_BLOCK", key_block)
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    S, T, n_dec, page = 22, 8, 4, 4
    toks = rng.integers(0, cfg.vocab_size, S + n_dec).astype(np.int32)
    want = _forward_logits(params, cfg, jnp.asarray(toks[None]))[0]
    max_len, n_blocks = 32, 20
    cache = init_cache(cfg, 2, max_len, n_blocks=n_blocks, page_size=page)
    tables = np.zeros((2, max_len // page), np.int32)
    tables[1] = rng.permutation(np.arange(1, n_blocks))[:max_len // page]
    tables = jnp.asarray(tables)
    for off in range(0, S, T):
        n = min(T, S - off)
        buf = np.zeros((1, T), np.int32)
        buf[0, :n] = toks[off:off + n]
        lg, cache = extend_step(params, cfg, cache, jnp.asarray(buf),
                                jnp.int32(off), jnp.int32(n), jnp.int32(1),
                                block_tables=tables)
    np.testing.assert_allclose(np.asarray(lg[0, 0]), np.asarray(want[S - 1]),
                               atol=ATOL, rtol=0)
    for i in range(n_dec):
        feed = jnp.asarray([[0], [toks[S + i]]], jnp.int32)
        pos = jnp.asarray([0, S + i], jnp.int32)
        lg, cache = decode_step(params, cfg, cache, feed, pos,
                                active=jnp.asarray([False, True]),
                                block_tables=tables)
        np.testing.assert_allclose(np.asarray(lg[1, 0]),
                                   np.asarray(want[S + i]), atol=ATOL,
                                   rtol=0)


@pytest.mark.parametrize("backend", ["ref", "interpret"])
def test_engine_with_prefix_cache_matches_full_forward(backend):
    """The normal path: ServeEngine with the paged latent pool, chunked
    prefill and the prefix cache on. The second request shares the first
    one's prompt pages (a prefix hit); every token served is the full
    forward's argmax with a logit gap of 0 to float32 rounding. The kv
    rank of 128 puts the latent rows in reach of the Pallas kernel, which
    ``interpret`` runs."""
    cfg = _cfg()
    cfg = cfg.replace(mla=dataclasses.replace(cfg.mla, kv_lora_rank=128))
    params = init(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(5)
    shared = rng.integers(0, cfg.vocab_size, 24).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, cfg.vocab_size, n)
                               .astype(np.int32)]) for n in (5, 11)]
    eng = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                      page_size=4, prefill_chunk=8, prefix_cache=True,
                      kernel_backend=backend)
    from repro.kernels.dispatch import registry
    registry.clear_resolutions()
    eng.submit(Request(uid=0, prompt=prompts[0], max_new_tokens=6,
                       temperature=0.0))
    eng.run([])
    eng.submit(Request(uid=1, prompt=prompts[1], max_new_tokens=6,
                       temperature=0.0))
    eng.run([])
    assert eng.stats["prefix_hits"] >= 1
    if backend == "interpret":
        assert registry.resolutions()[("paged_mla", "pallas")] > 0
    for uid, prompt in enumerate(prompts):
        served = np.asarray(eng.results[uid].tokens, np.int32)
        seq = np.concatenate([prompt, served[:-1]])
        lg = np.asarray(_forward_logits(params, cfg,
                                        jnp.asarray(seq[None]))[0])
        rows = lg[len(prompt) - 1:]
        gap = rows.max(-1) - rows[np.arange(len(served)), served]
        assert gap.max() <= ATOL, gap


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5),
                                       ("bfloat16", 2e-2)])
def test_paged_mla_kernel_matches_oracle(dtype, tol):
    """The Pallas kernel in interpret mode against the gather oracle, on a
    pool stacked over two layers read at layer 1: lengths inside one page,
    across a loop step and ending mid-page, several pages per step."""
    from repro.kernels.paged_mla import paged_mla
    from repro.kernels.ref import paged_mla_ref

    rng = np.random.default_rng(0)
    L, N, page, D, dv, H, P = 2, 24, 4, 256, 128, 4, 6
    dt = jnp.dtype(dtype)
    pool = jnp.asarray(rng.standard_normal((L, N, page, D)), dt)
    q = jnp.asarray(rng.standard_normal((3, H, D)) * 0.1, dt)
    tbl = jnp.asarray(rng.permutation(np.arange(1, N))[:3 * P].reshape(3, P),
                      jnp.int32)
    lens = jnp.asarray([3, 9, 24], jnp.int32)
    want = paged_mla_ref(q, pool, tbl, lens, 1, dv=dv).astype(jnp.float32)
    for pps in (1, 4):
        got = paged_mla(q, pool, tbl, lens, jnp.int32(1), dv=dv,
                        pages_per_step=pps, interpret=True)
        np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)),
                                   np.asarray(want), atol=tol, rtol=tol)


def test_yarn_matches_published_formulas():
    """``rope_inv_freq``/``yarn_mscale`` against a transcription of
    ``modeling_deepseek.py`` (``yarn_find_correction_range``,
    ``yarn_linear_ramp_mask``, ``yarn_get_mscale``) at DeepSeek-V2-Lite's
    rope dim, and the softmax scale of its latent attention."""
    cfg = get_arch("deepseek-v2-lite")
    sc = cfg.rope_scaling
    dim, base = 64, 10000.0

    def corr(rot):
        return (dim * math.log(4096 / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low, high = max(math.floor(corr(32)), 0), min(math.ceil(corr(1)),
                                                  dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (40 * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = inter * (1 - mask) + extra * mask
    np.testing.assert_allclose(rope_inv_freq(dim, base, sc), want,
                               rtol=1e-6)
    np.testing.assert_allclose(rope_inv_freq(dim, base, None), extra,
                               rtol=1e-6)
    assert yarn_mscale(40, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40) + 1)
    assert yarn_mscale(1.0, 0.707) == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attn_mod.mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


@pytest.mark.parametrize("shape", [(1, 32), (32, 1)],
                         ids=["chunk", "decode"])
def test_drop_free_experts_are_batch_invariant(shape):
    """One token alone against the same token batched behind 31 tokens
    routed to its experts, as a prefill chunk (one sequence of 32) and as a
    decode step (32 slots of one): at capacity 1.25 (ceil(32 x 2 / 8 x
    1.25) = 10 rows per expert) it is dropped and its output changes; the
    serving path drops nothing and gives it the same output to float32
    rounding."""
    cfg = _cfg(d_model=64).replace(moe=dataclasses.replace(
        reduced(get_arch("deepseek-v2-lite")).moe, capacity_factor=1.25))
    p = moe_mod.moe_init(jax.random.PRNGKey(1), cfg, jnp.float32)
    tok = jax.random.normal(jax.random.PRNGKey(2), (cfg.d_model,))
    batch = jnp.broadcast_to(tok, shape + (cfg.d_model,))   # one group
    alone = tok[None, None]
    assert moe_mod.capacity(cfg.moe, 32) == 10 < 32
    y1, _ = moe_mod.moe_forward(p, cfg, alone, compute_dtype=jnp.float32,
                                drop_free=True)
    yb, _ = moe_mod.moe_forward(p, cfg, batch, compute_dtype=jnp.float32,
                                drop_free=True)
    last = yb.reshape(32, cfg.d_model)[-1]
    np.testing.assert_allclose(np.asarray(last), np.asarray(y1[0, 0]),
                               atol=1e-5)
    dropped, _ = moe_mod.moe_forward(p, cfg, batch,
                                     compute_dtype=jnp.float32)
    assert float(jnp.abs(dropped.reshape(32, cfg.d_model)[-1]
                         - y1[0, 0]).max()) > 1e-3


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_engine_serves_in_bfloat16(paged):
    """Both cache layouts serve the reduced config in its own bf16 compute
    dtype: batched decode over several slots and chunked prefill."""
    cfg = reduced(get_arch("deepseek-v2-lite"))
    params = init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_slots=3, max_len=48, paged=paged,
                      page_size=4, prefill_chunk=8)
    res = eng.run([Request(uid=i, prompt=np.arange(5 + 7 * i,
                                                   dtype=np.int32),
                           max_new_tokens=4, temperature=0.0)
                   for i in range(3)])
    assert [len(r.tokens) for r in res] == [4, 4, 4]


def test_moe_row_counters():
    """The engine counts routed and computed expert rows of each decode
    step and prefill chunk from the step's static shapes."""
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_slots=3, max_len=32, paged=True,
                      page_size=4, prefill_chunk=8)
    eng.run([Request(uid=0, prompt=np.arange(10, dtype=np.int32),
                     max_new_tokens=3, temperature=0.0)])
    k, n_moe = cfg.moe.top_k, 2
    chunks, steps = eng.stats["prefill_chunks"], eng.stats["decode_steps"]
    assert (chunks, steps) == (2, 2)
    # 10 prompt tokens in two chunks of 8, then 2 decode steps of 1 token
    assert eng.stats["moe_rows_routed"] == (10 + 2) * k * n_moe
    assert eng.stats["moe_rows_computed"] == (
        moe_mod.rows_computed(cfg.moe, 8) * chunks
        + moe_mod.rows_computed(cfg.moe, 3) * steps) * n_moe


def test_unsupported_latent_serving_is_refused():
    """What latent attention does not serve yet fails with a ValueError
    that names it: query compression, quantized latent pools, a sharded
    serve, speculative verification."""
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="query compression"):
        cfg.replace(mla=dataclasses.replace(cfg.mla, q_lora_rank=32))
    with pytest.raises(ValueError, match="quantized latent"):
        ServeEngine(cfg.replace(kv_dtype="int8"), params, max_slots=2,
                    max_len=32, paged=True)
    with pytest.raises(ValueError, match="quantized latent"):
        init_cache(cfg.replace(kv_dtype="int8"), 2, 32, n_blocks=9,
                   page_size=4)
    with pytest.raises(ValueError, match="speculative"):
        ServeEngine(cfg, params, max_slots=2, max_len=32, paged=True,
                    draft_model="qwen3-0.6b")
    with pytest.raises(ValueError, match="Partitioner"):
        ServeEngine(cfg, params, max_slots=2, max_len=32, paged=True,
                    part=object())
    from repro.models import verify_step
    cache = init_cache(cfg, 2, 32, n_blocks=17, page_size=4)
    with pytest.raises(ValueError, match="verify_step"):
        verify_step(params, cfg, cache, jnp.zeros((2, 2), jnp.int32),
                    jnp.zeros((2,), jnp.int32), jnp.full((2,), 2, jnp.int32),
                    block_tables=jnp.zeros((2, 8), jnp.int32))
