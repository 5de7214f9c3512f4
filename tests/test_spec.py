"""Speculative decoding + COW-forked parallel sampling (repro.spec).

Covers the three layers separately and end to end:

* the fused sampler's top-k / top-p filters (unit-level, exact sets);
* the rejection-sampling acceptance rule — exact greedy parity against an
  argmax chain, and a chi-squared check that the *marginal* distribution
  of the first committed token matches the verifier's own sampling
  distribution no matter how wrong the draft is (the Leviathan guarantee:
  speculation changes latency, never the distribution);
* the serving engine — greedy token parity with and without a draft,
  per-request RNG reproducibility independent of batch composition,
  Request(n=4) fan-out sharing, and leak-free drains for both paths.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.models import init as model_init
from repro.serve.engine import Request, ServeEngine
from repro.spec import (filter_logits, filtered_probs, sample_tokens,
                        speculative_accept)

# chi-squared critical values at alpha = 0.001 (no scipy on the container)
CHI2_999 = {7: 24.322, 15: 37.697, 31: 61.098}


def _cfg(**kw):
    base = dataclasses.replace(
        reduced(get_arch("qwen3-0.6b")), n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab_size=256,
        dtype="float32", paged_kv=True, page_size=8)
    return dataclasses.replace(base, **kw)


def _draft_cfg(cfg):
    return dataclasses.replace(cfg, n_layers=1, d_model=32, n_heads=2,
                               n_kv_heads=1, d_ff=64)


# ---------------------------------------------------------------------------
# fused sampler filters
# ---------------------------------------------------------------------------
def test_top_k_filter_keeps_exactly_k():
    logits = jnp.asarray([[3.0, 1.0, 2.0, 0.0, -1.0]])
    out = np.asarray(filter_logits(logits, jnp.asarray([2]),
                                   jnp.asarray([1.0])))
    kept = np.where(out[0] > -1e29)[0]
    assert set(kept.tolist()) == {0, 2}, "top-2 must keep the two best"


def test_top_p_filter_nucleus():
    # probs = [0.5, 0.25, 0.125, 0.125] -> top_p=0.6 keeps {0, 1}: token 0
    # alone covers 0.5 < 0.6, so token 1 (prior mass 0.5 < p) joins
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.125, 0.125]]))
    out = np.asarray(filter_logits(logits, jnp.asarray([0]),
                                   jnp.asarray([0.6])))
    kept = np.where(out[0] > -1e29)[0]
    assert set(kept.tolist()) == {0, 1}


def test_top_p_always_keeps_best():
    logits = jnp.asarray([[1.0, 0.9, 0.8]])
    out = np.asarray(filter_logits(logits, jnp.asarray([0]),
                                   jnp.asarray([1e-9])))
    kept = np.where(out[0] > -1e29)[0]
    assert kept.tolist() == [0], "a tiny top_p still keeps the argmax"


def test_filters_disabled_are_identity():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
    out = filter_logits(logits, jnp.zeros(3, jnp.int32), jnp.ones(3))
    np.testing.assert_allclose(np.asarray(out), np.asarray(logits))


def _sample_tokens_full(logits, temps, top_k, top_p, keys):
    """The fused sampler's formula before it branched on the knobs: every
    row filtered and drawn, greedy rows selected out afterwards."""
    greedy = jnp.argmax(logits, axis=-1)
    t = jnp.where(temps <= 0, 1.0, temps)[:, None]
    f = filter_logits(logits.astype(jnp.float32) / t, top_k, top_p)
    sampled = jax.vmap(
        lambda k, row: jax.random.categorical(k, row))(keys, f)
    return jnp.where(temps <= 0, greedy, sampled).astype(jnp.int32)


@pytest.mark.parametrize("temps,top_k,top_p", [
    # every row greedy, filters set on some of them
    ([0.0, 0.0, 0.0, 0.0, 0.0, 0.0], [0, 5, 0, 1, 0, 3],
     [1.0, 1.0, 0.5, 1.0, 0.9, 0.2]),
    # one sampled row with top-k among greedy rows
    ([0.0, 0.8, 0.0, 0.0, 0.0, 0.0], [0, 7, 0, 0, 2, 0],
     [1.0, 1.0, 1.0, 0.3, 1.0, 1.0]),
    # sampled rows with no filter (greedy rows may still carry one)
    ([1.0, 0.0, 0.7, 1.3, 0.0, 2.0], [0, 4, 0, 0, 0, 0],
     [1.0, 1.0, 1.0, 1.0, 0.5, 1.0]),
    # every row sampled with top-p
    ([1.0, 0.5, 0.9, 1.2, 2.0, 0.3], [0, 0, 0, 0, 0, 0],
     [0.9, 0.5, 0.95, 0.8, 0.99, 0.7]),
], ids=["all-greedy", "one-top-k", "unfiltered", "all-top-p"])
def test_sample_tokens_matches_full_formula(temps, top_k, top_p):
    rng = np.random.default_rng(5)
    B, V = len(temps), 64
    logits = jnp.asarray(rng.normal(scale=2.0, size=(B, V)), jnp.float32)
    args = (logits, jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32))
    for seed in range(8):
        keys = jax.random.split(jax.random.PRNGKey(seed), B)
        got = jax.jit(sample_tokens)(*args, keys)
        want = jax.jit(_sample_tokens_full)(*args, keys)
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# acceptance rule
# ---------------------------------------------------------------------------
def test_accept_greedy_parity_full_chain():
    """At temperature 0 with a draft that proposes the argmax chain, every
    token is accepted and the bonus token is the verifier's next argmax."""
    rng = np.random.default_rng(1)
    k, V = 3, 11
    logits = jnp.asarray(rng.normal(size=(1, k + 1, V)), jnp.float32)
    argmax = np.asarray(jnp.argmax(logits, -1))[0]          # (k+1,)
    draft = jnp.asarray(argmax[None, :k], jnp.int32)
    dprobs = jnp.asarray(jax.nn.one_hot(draft, V), jnp.float32)
    out, n_acc = speculative_accept(
        logits, draft, dprobs, jnp.zeros(1), jnp.zeros(1, jnp.int32),
        jnp.ones(1), jax.random.PRNGKey(0)[None])
    assert int(n_acc[0]) == k
    np.testing.assert_array_equal(np.asarray(out)[0], argmax)


def test_accept_greedy_rejects_at_first_mismatch():
    rng = np.random.default_rng(2)
    k, V = 4, 7
    logits = jnp.asarray(rng.normal(size=(1, k + 1, V)), jnp.float32)
    argmax = np.asarray(jnp.argmax(logits, -1))[0]
    draft_np = argmax[:k].copy()
    draft_np[2] = (draft_np[2] + 1) % V                     # diverge at 2
    draft = jnp.asarray(draft_np[None], jnp.int32)
    dprobs = jnp.asarray(jax.nn.one_hot(draft, V), jnp.float32)
    out, n_acc = speculative_accept(
        logits, draft, dprobs, jnp.zeros(1), jnp.zeros(1, jnp.int32),
        jnp.ones(1), jax.random.PRNGKey(3)[None])
    assert int(n_acc[0]) == 2
    # committed prefix: two accepted draft tokens + the verifier's argmax
    # at the rejection point (greedy residual = argmax of p)
    np.testing.assert_array_equal(np.asarray(out)[0, :3], argmax[:3])


@pytest.mark.parametrize("qkind", ["uniform", "skewed", "offbyone"])
def test_accept_preserves_marginal_distribution(qkind):
    """Chi-squared: over many PRNG keys, the first committed token's
    histogram must match the verifier's filtered softmax row — whatever
    the draft distribution was. This is the whole point of the rule."""
    V, k, N = 8, 2, 6000
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(1, k + 1, V)), jnp.float32)
    p0 = np.asarray(filtered_probs(logits[:, 0], jnp.ones(1),
                                   jnp.zeros(1, jnp.int32), jnp.ones(1)))[0]
    if qkind == "uniform":
        q = np.full((1, k, V), 1.0 / V, np.float32)
    elif qkind == "skewed":
        raw = rng.random((1, k, V)).astype(np.float32) ** 4
        q = raw / raw.sum(-1, keepdims=True)
    else:   # deterministic draft proposing a near-argmax token
        tok = (int(np.argmax(p0)) + 1) % V
        q = np.asarray(jax.nn.one_hot(np.full((1, k), tok), V), np.float32)
    dtoks = jnp.asarray(
        rng.choice(V, size=(N, 1, k), p=q[0, 0] / q[0, 0].sum()), jnp.int32)
    keys = jax.vmap(jax.random.PRNGKey)(jnp.arange(N))

    def one(key, dt):
        out, _ = speculative_accept(
            logits, dt, jnp.asarray(q), jnp.ones(1),
            jnp.zeros(1, jnp.int32), jnp.ones(1), key[None])
        return out[0, 0]
    first = np.asarray(jax.jit(jax.vmap(one))(keys, dtoks))
    obs = np.bincount(first, minlength=V).astype(np.float64)
    exp = p0.astype(np.float64) * N
    keep = exp > 5            # standard chi-squared validity threshold
    chi2 = float(((obs[keep] - exp[keep]) ** 2 / exp[keep]).sum())
    df = int(keep.sum()) - 1
    crit = CHI2_999.get(df, CHI2_999[7] * (df + 1) / 8)
    assert chi2 < crit, (chi2, crit, obs, exp)


# ---------------------------------------------------------------------------
# serving engine: speculative decoding
# ---------------------------------------------------------------------------
def _run_engine(cfg, params, prompts, *, draft=None, dparams=None, spec_k=4,
                max_new=10, **req_kw):
    eng = ServeEngine(cfg, params, max_slots=4, max_len=96, paged=True,
                      draft_model=draft, draft_params=dparams, spec_k=spec_k)
    reqs = [Request(uid=i, prompt=p, max_new_tokens=max_new, **req_kw)
            for i, p in enumerate(prompts)]
    res = eng.run(reqs)
    return res, eng


def test_engine_spec_greedy_parity_and_leakfree():
    cfg = _cfg()
    dcfg = _draft_cfg(cfg)
    params = model_init(jax.random.PRNGKey(0), cfg)
    dparams = model_init(jax.random.PRNGKey(1), dcfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 255, size=n).astype(np.int32)
               for n in (12, 7, 20)]
    base, _ = _run_engine(cfg, params, prompts)
    spec, eng = _run_engine(cfg, params, prompts, draft=dcfg,
                            dparams=dparams)
    assert [r.tokens for r in base] == [r.tokens for r in spec]
    assert eng.stats["spec_turns"] > 0
    # leak-free drain: every speculative page rolled back
    assert (eng.allocator.n_free + eng.allocator.n_evictable
            == eng.allocator.capacity)


def test_engine_spec_self_draft_accepts_everything():
    """Draft == verifier: every proposal must be accepted (the acceptance
    ratio p/q is identically 1), so decode takes ~1/(k+1) the turns."""
    cfg = _cfg()
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, 255, size=10).astype(np.int32)]
    res, eng = _run_engine(cfg, params, prompts, draft=cfg, dparams=params,
                           max_new=12)
    assert res[0].finish_reason == "length"
    assert eng.stats["spec_accepted"] == eng.stats["spec_proposed"]


def test_engine_spec_requires_paged_all_full():
    cfg = _cfg(paged_kv=False)
    params = model_init(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="speculative"):
        ServeEngine(cfg, params, paged=False, draft_model=_draft_cfg(cfg),
                    spec_k=2)


# ---------------------------------------------------------------------------
# serving engine: per-request RNG + filtered sampling
# ---------------------------------------------------------------------------
def test_request_seed_independent_of_batch():
    """The same (prompt, seed) request must sample the same tokens whether
    it runs alone or next to other traffic — the engine-global key
    order-dependence this subsystem removed."""
    cfg = _cfg()
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(2)
    probe = rng.integers(1, 255, size=9).astype(np.int32)
    other = [rng.integers(1, 255, size=n).astype(np.int32)
             for n in (14, 6, 11)]
    [alone], _ = _run_engine(cfg, params, [probe], temperature=1.0, seed=123)
    crowd, _ = _run_engine(cfg, params, [probe] + other, temperature=1.0,
                           seed=123)
    assert alone.tokens == crowd[0].tokens


def test_top_k_sampling_stays_in_top_k():
    cfg = _cfg()
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, 255, size=8).astype(np.int32)
    # top_k=1 at any temperature is greedy: compare to the greedy stream
    [greedy], _ = _run_engine(cfg, params, [prompt])
    [k1], _ = _run_engine(cfg, params, [prompt], temperature=1.0, seed=9,
                          top_k=1)
    assert k1.tokens == greedy.tokens


# ---------------------------------------------------------------------------
# serving engine: COW-forked parallel sampling
# ---------------------------------------------------------------------------
def test_fork_n4_distinct_streams_and_shared_pages():
    cfg = _cfg()
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, 255, size=48).astype(np.int32)
    eng = ServeEngine(cfg, params, max_slots=6, max_len=128, paged=True)
    [res] = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=8,
                             temperature=1.0, seed=7, n=4)])
    assert res.finish_reason == "length" and len(res.children) == 3
    assert all(c.finish_reason == "length" and len(c.tokens) == 8
               for c in res.children)
    seqs = {tuple(res.tokens)} | {tuple(c.tokens) for c in res.children}
    assert len(seqs) == 4, "children must diverge from the parent"
    assert eng.stats["forks"] == 3 and eng.stats["fork_shared_blocks"] > 0
    # leak-free drain: shared refcounts fully unwound
    assert (eng.allocator.n_free + eng.allocator.n_evictable
            == eng.allocator.capacity)
    # fan-out fresh KV < 2x a single request's (shared pages ride free)
    single = ServeEngine(cfg, params, max_slots=6, max_len=128, paged=True)
    single.run([Request(uid=0, prompt=prompt, max_new_tokens=8,
                        temperature=1.0, seed=7)])
    assert (eng.stats["kv_bytes_alloc"]
            < 2 * single.stats["kv_bytes_alloc"])


def test_fork_greedy_children_match_parent():
    """At temperature 0 divergence is impossible: every forked child must
    reproduce the parent's greedy stream exactly (shared pages + the
    re-decoded boundary row carry identical state)."""
    cfg = _cfg()
    params = model_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, 255, size=21).astype(np.int32)
    eng = ServeEngine(cfg, params, max_slots=6, max_len=128, paged=True)
    [res] = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=6, n=3)])
    plain = ServeEngine(cfg, params, max_slots=6, max_len=128, paged=True)
    [pres] = plain.run([Request(uid=0, prompt=prompt, max_new_tokens=6)])
    assert res.tokens == pres.tokens
    assert all(c.tokens == pres.tokens for c in res.children)


def test_fork_rejected_on_dense_engine():
    cfg = _cfg(paged_kv=False)
    params = model_init(jax.random.PRNGKey(0), cfg)
    eng = ServeEngine(cfg, params, max_slots=4, max_len=96, paged=False)
    [res] = eng.run([Request(uid=0, prompt=np.arange(1, 9, dtype=np.int32),
                             max_new_tokens=4, n=2)])
    assert res.finish_reason == "rejected"
    assert "parallel sampling" in res.detail


def test_fork_with_spec_decoding_combined():
    """Both consumers at once: a fan-out served under a draft model still
    produces the greedy stream on every branch and drains leak-free."""
    cfg = _cfg()
    dcfg = _draft_cfg(cfg)
    params = model_init(jax.random.PRNGKey(0), cfg)
    dparams = model_init(jax.random.PRNGKey(1), dcfg)
    rng = np.random.default_rng(6)
    prompt = rng.integers(1, 255, size=17).astype(np.int32)
    eng = ServeEngine(cfg, params, max_slots=6, max_len=128, paged=True,
                      draft_model=dcfg, draft_params=dparams, spec_k=3)
    [res] = eng.run([Request(uid=0, prompt=prompt, max_new_tokens=7, n=3)])
    plain = ServeEngine(cfg, params, max_slots=6, max_len=128, paged=True)
    [pres] = plain.run([Request(uid=0, prompt=prompt, max_new_tokens=7)])
    assert res.tokens == pres.tokens
    assert all(c.tokens == pres.tokens for c in res.children)
    assert (eng.allocator.n_free + eng.allocator.n_evictable
            == eng.allocator.capacity)
