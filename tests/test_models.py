"""Per-arch smoke tests (reduced same-family configs) + decode equivalence.

Every one of the 11 registered architectures: instantiate reduced config, run a
forward/train step on CPU, assert output shapes and no NaNs. Then the serving
contract: prefill+decode logits == full-forward logits, per family.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced, strategy
from repro.models import decode_step, forward, init, lm_loss, logits_fn
from repro.models.cache import init_cache
from repro.optim.optimizers import adamw
from repro.train.train_step import make_train_step


def _batch_for(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    batch["targets"] = batch["tokens"]
    if cfg.frontend == "audio":
        batch["frames"] = jnp.asarray(rng.standard_normal(
            (B, cfg.encoder.n_frames, cfg.d_model)), jnp.bfloat16)
    if cfg.frontend == "vision":
        batch["extra_embeds"] = jnp.asarray(rng.standard_normal(
            (B, cfg.n_frontend_tokens, cfg.d_model)), jnp.bfloat16)
    return batch


# --------------------------------------------------------------------------
# smoke: forward + train step for every assigned arch (reduced config)
# --------------------------------------------------------------------------
def test_arch_smoke(arch_name):
    cfg = reduced(get_arch(arch_name))
    params = init(jax.random.PRNGKey(0), cfg)
    batch = _batch_for(cfg)
    # forward: shapes + finite
    hidden, _, aux = forward(params, cfg, batch["tokens"],
                             frames=batch.get("frames"),
                             extra_embeds=batch.get("extra_embeds"))
    S_tot = batch["tokens"].shape[1] + (cfg.n_frontend_tokens or 0)
    assert hidden.shape == (2, S_tot, cfg.d_model)
    assert bool(jnp.isfinite(hidden.astype(jnp.float32)).all())
    logits = logits_fn(params, cfg, hidden[:, -1:, :])
    assert logits.shape[-1] >= cfg.vocab_size
    assert bool(jnp.isfinite(logits[..., :cfg.vocab_size]).all())
    # one full train step: loss finite, params move
    opt = adamw(1e-3)
    step = jax.jit(make_train_step(cfg, opt, strategy("ramora")))
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    state2, metrics = step(state, {k: batch[k] for k in batch})
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        params, state2["params"])
    assert max(jax.tree.leaves(moved)) > 0, "no parameter moved"


def test_all_archs_registered():
    assert len(ARCHS) == 11
    fams = {c.family for c in ARCHS.values()}
    assert fams == {"dense", "hybrid", "moe", "ssm", "audio", "vlm"}


@pytest.mark.parametrize("name,total_b", [
    ("gemma2-27b", 27e9), ("deepseek-7b", 7e9), ("minicpm-2b", 2.7e9),
    ("qwen3-0.6b", 0.6e9), ("falcon-mamba-7b", 7e9),
    ("deepseek-moe-16b", 16e9), ("qwen2-moe-a2.7b", 14e9),
    ("llava-next-mistral-7b", 7e9), ("recurrentgemma-2b", 2.7e9),
    ("deepseek-v2-lite", 15.7e9),
])
def test_param_counts_match_billing(name, total_b):
    """Analytic param counts land within 25% of the arch's nameplate size."""
    pc = get_arch(name).param_count()
    assert 0.75 * total_b < pc["total"] < 1.35 * total_b, pc["total"]


def test_param_count_matches_init():
    """Analytic count equals the actual initialized leaf-count (tiny cfg)."""
    cfg = reduced(get_arch("deepseek-7b"))
    params = init(jax.random.PRNGKey(0), cfg)
    n_real = sum(x.size for x in jax.tree.leaves(params))
    n_analytic = cfg.param_count()["total"]
    # analytic skips norms/small vectors — must agree within 2%
    assert abs(n_real - n_analytic) / n_real < 0.02


# --------------------------------------------------------------------------
# decode equivalence: prefill + decode == full forward (per family)
# --------------------------------------------------------------------------
DECODE_FAMILIES = ["qwen3-0.6b", "gemma2-27b", "recurrentgemma-2b",
                   "falcon-mamba-7b", "qwen2-moe-a2.7b", "whisper-tiny",
                   "llava-next-mistral-7b"]


@pytest.mark.parametrize("name", DECODE_FAMILIES)
def test_prefill_decode_matches_forward(name):
    """logits(prefill S tokens, then decode token S) == logits(forward S+1).

    MoE archs need ample capacity: the full-sequence oracle drops tokens at
    capacity_factor 1.25 while single-token decode is drop-free by design.
    """
    cfg = reduced(get_arch(name)).replace(dtype="float32")
    if cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=64.0))
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    S = 24
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S + 1)), jnp.int32)
    kw = {}
    if cfg.frontend == "audio":
        kw["frames"] = jnp.asarray(rng.standard_normal(
            (1, cfg.encoder.n_frames, cfg.d_model)), jnp.float32)
    if cfg.frontend == "vision":
        kw["extra_embeds"] = jnp.asarray(rng.standard_normal(
            (1, cfg.n_frontend_tokens, cfg.d_model)), jnp.float32)

    # oracle: full forward over S+1 tokens, logits at the last position
    hidden, _, _ = forward(params, cfg, toks, **kw)
    want = logits_fn(params, cfg, hidden[:, -1:, :])[..., :cfg.vocab_size]

    # prefill S tokens, then one decode step for token S
    cache_t = init_cache(cfg, 1, 64)
    _, cache, _ = forward(params, cfg, toks[:, :S], cache=cache_t, **kw)
    n_extra = cfg.n_frontend_tokens if cfg.frontend == "vision" else 0
    got, _ = decode_step(params, cfg, cache, toks[:, S:S + 1],
                         jnp.asarray(S + n_extra, jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(want[0, 0]),
                               rtol=2e-3, atol=2e-3)


def test_decode_vector_pos_matches_scalar():
    """Per-slot (vector) positions == scalar path when all slots align."""
    cfg = reduced(get_arch("gemma2-27b")).replace(dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 8)), jnp.int32)
    cache_t = init_cache(cfg, 3, 64)
    _, cache, _ = forward(params, cfg, toks, cache=cache_t)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (3, 1)), jnp.int32)
    got_s, cache_s = decode_step(params, cfg, cache, nxt,
                                 jnp.asarray(8, jnp.int32))
    got_v, cache_v = decode_step(params, cfg, cache, nxt,
                                 jnp.full((3,), 8, jnp.int32))
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(got_v),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(jax.tree.leaves(cache_s), jax.tree.leaves(cache_v)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)


def test_local_ring_buffer_beyond_window():
    """Sliding-window ring cache stays exact once pos > window."""
    cfg = reduced(get_arch("gemma2-27b")).replace(dtype="float32", window=16)
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    S = 40  # > 2x window
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S + 1)), jnp.int32)

    hidden, _, _ = forward(params, cfg, toks)
    want = logits_fn(params, cfg, hidden[:, -1:, :])[..., :cfg.vocab_size]

    cache_t = init_cache(cfg, 1, 64)
    _, cache, _ = forward(params, cfg, toks[:, :S], cache=cache_t)
    got, _ = decode_step(params, cfg, cache, toks[:, S:], jnp.asarray(S))
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(want[0, 0]),
                               rtol=2e-3, atol=2e-3)


def test_decode_many_steps_matches_forward():
    """20 sequential decode steps == forward at every position (mamba)."""
    cfg = reduced(get_arch("falcon-mamba-7b")).replace(dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    S = 20
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, S)), jnp.int32)
    hidden, _, _ = forward(params, cfg, toks)
    want = logits_fn(params, cfg, hidden)[..., :cfg.vocab_size]

    cache = init_cache(cfg, 1, 64)
    outs = []
    for t in range(S):
        lg, cache = decode_step(params, cfg, cache, toks[:, t:t + 1],
                                jnp.asarray(t))
        outs.append(lg[0, 0])
    got = jnp.stack(outs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[0]),
                               rtol=2e-3, atol=2e-3)


# --------------------------------------------------------------------------
# architectural features
# --------------------------------------------------------------------------
def test_gemma2_softcaps_active():
    cfg = reduced(get_arch("gemma2-27b")).replace(dtype="float32")
    assert cfg.attn_softcap == 50.0 and cfg.final_softcap == 30.0
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.zeros((1, 4), jnp.int32)
    hidden, _, _ = forward(params, cfg, toks)
    logits = logits_fn(params, cfg, hidden)
    assert float(jnp.abs(logits[..., :cfg.vocab_size]).max()) <= 30.0


def test_chunked_loss_equals_full():
    cfg = reduced(get_arch("deepseek-7b")).replace(dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 40)), jnp.int32)
    full = lm_loss(params, cfg, toks, toks, loss_chunk=0)
    for lc in (8, 16, 33):  # 33: ragged tail path
        chunked = lm_loss(params, cfg, toks, toks, loss_chunk=lc)
        np.testing.assert_allclose(float(full), float(chunked),
                                   rtol=1e-5, atol=1e-5)


def test_scan_unroll_invariance():
    """scan_unroll changes lowering, never semantics."""
    cfg = reduced(get_arch("qwen3-0.6b")).replace(dtype="float32", n_layers=4)
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.arange(12)[None] % cfg.vocab_size, jnp.int32)
    l1 = lm_loss(params, cfg.replace(scan_unroll=1), toks, toks)
    l2 = lm_loss(params, cfg.replace(scan_unroll=2), toks, toks)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_remat_invariance():
    cfg = reduced(get_arch("qwen3-0.6b")).replace(dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(np.arange(16)[None] % cfg.vocab_size, jnp.int32)

    def loss(c):
        return lm_loss(params, c, toks, toks)

    g1 = jax.grad(lambda p: lm_loss(p, cfg.replace(remat="none"), toks, toks))(params)
    g2 = jax.grad(lambda p: lm_loss(p, cfg.replace(remat="block"), toks, toks))(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)
