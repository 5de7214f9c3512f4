"""Serving engine: continuous batching == sequential decode; slot lifecycle;
paged (block-pool) vs dense cache parity; chunked prefill; block accounting;
prefix-cache sharing (refcounted COW blocks) with stateful fuzz coverage."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.configs import LayerSpec, get_arch, reduced
from repro.models import decode_step, forward, init, logits_fn
from repro.models.cache import init_cache
from repro.serve import Request, ServeEngine


def _cfg(**kw):
    return reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32", **kw)


def _local_cfg():
    """Sliding-window (ring-buffer) attention config."""
    return _cfg(pattern=(LayerSpec("local", "dense"),), window=8)


def _rglru_cfg():
    return reduced(get_arch("recurrentgemma-2b")).replace(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32", window=8)


def _mamba_cfg():
    return reduced(get_arch("falcon-mamba-7b")).replace(
        n_layers=2, d_model=64, vocab_size=256, dtype="float32")


def _ref_greedy(cfg, params, prompt, max_new, max_len=96):
    cache_t = init_cache(cfg, 1, max_len)
    hidden, cache, _ = forward(params, cfg, jnp.asarray(prompt)[None],
                               cache=cache_t)
    lg = logits_fn(params, cfg, hidden[:, -1:, :])[..., :cfg.vocab_size]
    toks = [int(jnp.argmax(lg[0, 0]))]
    pos = len(prompt)
    for _ in range(max_new - 1):
        lg, cache = decode_step(params, cfg, cache,
                                jnp.asarray([[toks[-1]]], jnp.int32),
                                jnp.asarray(pos, jnp.int32))
        toks.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return toks


def _mixed_requests(cfg, n, seed, lo=3, hi=14, new_lo=2, new_hi=7):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        rng.integers(lo, hi)).astype(np.int32),
                    max_new_tokens=int(rng.integers(new_lo, new_hi)))
            for i in range(n)]


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_continuous_batching_matches_sequential(setup):
    cfg, params = setup
    reqs = _mixed_requests(cfg, 7, seed=0, lo=3, hi=12, new_lo=2, new_hi=6)
    engine = ServeEngine(cfg, params, max_slots=3, max_len=96)
    results = engine.run(reqs)
    assert all(r.finish_reason == "length" for r in results)
    for r, req in zip(results, reqs):
        ref = _ref_greedy(cfg, params, req.prompt, req.max_new_tokens)
        assert r.tokens == ref, f"uid {r.uid}"


def test_slot_reuse_exceeds_pool(setup):
    cfg, params = setup
    rng = np.random.default_rng(1)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=96)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, 5).astype(np.int32),
                    max_new_tokens=3) for i in range(5)]
    results = engine.run(reqs)
    assert len(results) == 5
    assert engine.stats["prefills"] == 5
    assert not engine.active.any()


def test_eos_stops_early(setup):
    cfg, params = setup
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 256, 6).astype(np.int32)
    # find what greedy emits first, then declare that token the EOS
    first = _ref_greedy(cfg, params, prompt, 1)[0]
    engine = ServeEngine(cfg, params, max_slots=1, max_len=96, eos_id=first)
    [res] = engine.run([Request(uid=0, prompt=prompt, max_new_tokens=10)])
    assert res.finish_reason == "eos"
    assert len(res.tokens) == 1


def test_overflow_rejected_gracefully(setup):
    """A request that cannot fit finishes with 'rejected' instead of
    crashing the engine loop; later requests keep being served."""
    cfg, params = setup
    rng = np.random.default_rng(4)
    engine = ServeEngine(cfg, params, max_slots=1, max_len=16)
    bad = Request(uid=0, prompt=np.zeros(14, np.int32), max_new_tokens=8)
    good = Request(uid=1, prompt=rng.integers(0, 256, 4).astype(np.int32),
                   max_new_tokens=3)
    res_bad, res_good = engine.run([bad, good])
    assert res_bad.finish_reason == "rejected"
    assert res_bad.tokens == []
    assert res_good.finish_reason == "length"
    assert len(res_good.tokens) == 3
    assert engine.stats["rejected"] == 1


def test_host_upload_owns_its_bytes():
    """The engine mutates its slot arrays right after dispatching a step.
    An upload that still reads the NumPy buffer (``jnp.asarray`` on the CPU
    transfers asynchronously from it) would pick up the later values."""
    from repro.serve.engine import _h2d
    for _ in range(5):
        host = np.zeros(1 << 22, np.float32)
        dev = _h2d(host)
        host[:] = 1.0
        assert float(dev.sum()) == 0.0


def test_prefill_jit_cache_reused(setup):
    cfg, params = setup
    rng = np.random.default_rng(3)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=96)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, 8).astype(np.int32),
                    max_new_tokens=2) for i in range(6)]
    engine.run(reqs)
    assert engine.stats["prefill_recompiles"] == 1


def test_chunked_prefill_one_compile_across_lengths(setup):
    """Distinct prompt lengths (shorter and longer than the chunk) all ride
    the ONE compiled extend_step shape — no per-length jit cache."""
    cfg, params = setup
    engine = ServeEngine(cfg, params, max_slots=2, max_len=96,
                         prefill_chunk=5)
    rng = np.random.default_rng(5)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, 256, length).astype(np.int32),
                    max_new_tokens=2)
            for i, length in enumerate((3, 5, 7, 11, 16, 23))]
    results = engine.run(reqs)
    assert engine.stats["prefill_recompiles"] == 1
    assert engine.stats["prefill_chunks"] == sum(
        -(-len(r.prompt) // 5) for r in reqs)
    for r, req in zip(results, reqs):
        assert r.tokens == _ref_greedy(cfg, params, req.prompt,
                                       req.max_new_tokens), f"uid {r.uid}"


# --------------------------------------------------------------------------
# paged (block-pool) cache
# --------------------------------------------------------------------------
@pytest.mark.parametrize("make_cfg", [_cfg, _local_cfg, _rglru_cfg,
                                      _mamba_cfg],
                         ids=["global", "local-window", "rglru", "mamba"])
def test_paged_matches_dense_token_for_token(make_cfg):
    """Greedy parity across interleaved admits/finishes: paged and dense
    engines emit identical tokens, which also match sequential decode —
    including eviction-sensitive caches (ring window, recurrent conv state)
    decoded past the window."""
    cfg = make_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    # prompts longer than the window (8) and generations pushing past it
    reqs = _mixed_requests(cfg, 6, seed=7, lo=4, hi=20, new_lo=3, new_hi=9)
    outs = {}
    for paged in (False, True):
        engine = ServeEngine(cfg, params, max_slots=3, max_len=64,
                             paged=paged, page_size=8, prefill_chunk=6)
        results = engine.run([Request(uid=r.uid, prompt=r.prompt,
                                      max_new_tokens=r.max_new_tokens)
                              for r in reqs])
        outs[paged] = [r.tokens for r in results]
        if paged:
            assert engine.allocator.n_free == engine.allocator.capacity, \
                "blocks leaked after all requests finished"
    assert outs[True] == outs[False]
    for toks, req in zip(outs[True], reqs):
        assert toks == _ref_greedy(cfg, params, req.prompt,
                                   req.max_new_tokens, max_len=64), \
            f"uid {req.uid}"


def test_paged_kv_memory_proportional_to_lengths(setup):
    """Paged admission charges blocks for actual prompt+budget tokens; at
    mixed lengths that is far below the dense max_len-per-slot reservation."""
    cfg, params = setup
    reqs = _mixed_requests(cfg, 6, seed=9, lo=3, hi=16, new_lo=2, new_hi=6)
    stats = {}
    for paged in (False, True):
        engine = ServeEngine(cfg, params, max_slots=3, max_len=96,
                             paged=paged, page_size=8)
        engine.run([Request(uid=r.uid, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens) for r in reqs])
        stats[paged] = engine.stats["kv_bytes_alloc"]
    assert stats[True] < stats[False] / 2


def test_block_pool_backpressure():
    """With a pool too small for all requests at once, admission waits for
    blocks to free (FCFS) and every request still completes; a request that
    can never fit the pool is rejected, not deadlocked."""
    cfg = _cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    # pool of 6 usable blocks * 8 rows = 48 tokens; each request needs
    # 16 tokens -> 2 blocks; 4 slots but only 3 requests fit at once
    engine = ServeEngine(cfg, params, max_slots=4, max_len=64, paged=True,
                         page_size=8, max_blocks=7)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, 12).astype(np.int32),
                    max_new_tokens=4) for i in range(6)]
    reqs.append(Request(uid=6,                       # needs 7 > 6 blocks
                        prompt=rng.integers(0, 256, 50).astype(np.int32),
                        max_new_tokens=4))
    results = engine.run(reqs)
    assert [r.finish_reason for r in results[:6]] == ["length"] * 6
    assert results[6].finish_reason == "rejected"
    assert engine.allocator.n_free == engine.allocator.capacity


# --------------------------------------------------------------------------
# prefix caching (refcounted copy-on-write block sharing)
# --------------------------------------------------------------------------
def _shared_prefix_requests(cfg, n, seed, sys_len=16, page=8):
    """Mixed trace: most prompts extend a shared system prefix (full- or
    half-page matches), the rest are cold; lengths and budgets vary."""
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, sys_len).astype(np.int32)
    reqs = []
    for i in range(n):
        r = rng.random()
        if r < 0.5:      # shared prefix + unique tail
            tail = rng.integers(0, cfg.vocab_size, rng.integers(1, 9))
            prompt = np.concatenate([sys_prompt, tail.astype(np.int32)])
        elif r < 0.7:    # exact resubmission (page-aligned full match: COW)
            prompt = sys_prompt.copy()
        else:            # cold prompt
            prompt = rng.integers(0, cfg.vocab_size,
                                  rng.integers(3, 20)).astype(np.int32)
        reqs.append(Request(uid=i, prompt=prompt,
                            max_new_tokens=int(rng.integers(2, 7))))
    return reqs


def _run_interleaved(cfg, params, reqs, submit_at, *, prefix_cache, **kw):
    """Drive the engine with requests arriving at randomized step offsets
    (admit/decode/finish interleavings differ per schedule)."""
    engine = ServeEngine(cfg, params, paged=True, prefix_cache=prefix_cache,
                         **kw)
    order = sorted(range(len(reqs)), key=lambda i: submit_at[i])
    i, step = 0, 0
    while i < len(order) or engine.queue or engine.active.any():
        while i < len(order) and submit_at[order[i]] <= step:
            engine.submit(reqs[order[i]])
            i += 1
        engine.step()
        step += 1
        assert step < 5000, "engine failed to drain"
    return engine


def _assert_drained_leak_free(engine):
    """After drain: no live blocks, and free + cached cover the capacity."""
    alloc = engine.allocator
    assert alloc.n_live == 0
    cached = (0 if engine.prefix_index is None
              else engine.prefix_index.n_evictable(alloc))
    assert alloc.n_free + cached == alloc.capacity, \
        (alloc.n_free, cached, alloc.capacity)


def _fuzz_once(make_cfg, seed, max_blocks=None):
    cfg = make_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(seed)
    reqs = _shared_prefix_requests(cfg, 7, seed)
    submit_at = rng.integers(0, 25, len(reqs))
    outs = {}
    for pc in (False, True):
        engine = _run_interleaved(
            cfg, params,
            [Request(uid=r.uid, prompt=r.prompt,
                     max_new_tokens=r.max_new_tokens) for r in reqs],
            submit_at, prefix_cache=pc, max_slots=3, max_len=64,
            page_size=8, prefill_chunk=6, max_blocks=max_blocks)
        outs[pc] = [engine.results[r.uid].tokens for r in reqs]
        assert all(engine.results[r.uid].finish_reason == "length"
                   for r in reqs)
        _assert_drained_leak_free(engine)
    assert outs[True] == outs[False], \
        "prefix cache changed greedy outputs"


@pytest.mark.property
@pytest.mark.parametrize("make_cfg", [_cfg, _local_cfg],
                         ids=["global", "local-window"])
@pytest.mark.parametrize("seed", [0, 1])
def test_prefix_cache_fuzz_seeded(make_cfg, seed):
    """Stateful serving fuzz: randomized admit/decode/finish interleavings
    with the prefix cache on vs off emit identical greedy tokens and leak
    no blocks, on all-full and local-window paged configs (the latter is
    prefix-incapable and must degrade to cold serving, not corrupt)."""
    _fuzz_once(make_cfg, seed)


@pytest.mark.property
@settings(max_examples=5, deadline=None)
@given(st.integers(100, 10_000))
def test_prefix_cache_fuzz_hypothesis(seed):
    """Hypothesis-driven schedules over the all-full config, including a
    pool small enough (max_blocks=13) that admission backpressure and LRU
    eviction of cached blocks interleave with the hits."""
    _fuzz_once(_cfg, seed, max_blocks=13)


def test_prefix_hit_skips_prefill_and_shares_blocks(setup):
    """A warm cache turns the shared-prefix prefill into a tail-only
    extend: fewer chunks, fewer fresh KV bytes, identical greedy tokens."""
    cfg, params = setup
    rng = np.random.default_rng(21)
    sys_prompt = rng.integers(0, 256, 24).astype(np.int32)   # 3 pages
    mk = lambda: [Request(uid=i, prompt=np.concatenate(
                      [sys_prompt, rng2.integers(0, 256, 5).astype(np.int32)]),
                      max_new_tokens=4)
                  for i, rng2 in enumerate(np.random.default_rng(22).spawn(4))]
    stats = {}
    outs = {}
    for pc in (False, True):
        engine = ServeEngine(cfg, params, max_slots=2, max_len=64,
                             paged=True, page_size=8, prefill_chunk=8,
                             prefix_cache=pc)
        [w] = engine.run([Request(uid=99, prompt=sys_prompt,
                                  max_new_tokens=2)])   # warms the cache
        kv0 = engine.stats["kv_bytes_alloc"]
        res = engine.run(mk())
        outs[pc] = [r.tokens for r in res]
        stats[pc] = dict(engine.stats, kv_delta=engine.stats["kv_bytes_alloc"]
                         - kv0)
        _assert_drained_leak_free(engine)
    assert outs[True] == outs[False]
    assert stats[True]["prefix_hits"] == 4
    assert stats[True]["prefix_hit_tokens"] == 4 * 24
    # 3 of each request's 4 pages ride in shared: fewer chunks, fewer bytes
    assert stats[True]["prefill_chunks"] < stats[False]["prefill_chunks"]
    assert stats[True]["kv_delta"] < stats[False]["kv_delta"]
    # the shared pages stay resident (refcount-0 cached) after the drain
    assert stats[True]["kv_bytes_cached"] > 0


def test_prefix_full_match_triggers_cow(setup):
    """Resubmitting a page-aligned prompt matches every page; the final
    token still recomputes (its logits seed decode), so the last shared
    page is privatized copy-on-write and greedy outputs stay exact."""
    cfg, params = setup
    prompt = np.random.default_rng(23).integers(0, 256, 16).astype(np.int32)
    outs = {}
    for pc in (False, True):
        engine = ServeEngine(cfg, params, max_slots=2, max_len=64,
                             paged=True, page_size=8, prefill_chunk=8,
                             prefix_cache=pc)
        r1 = engine.run([Request(uid=0, prompt=prompt, max_new_tokens=5)])
        r2 = engine.run([Request(uid=1, prompt=prompt.copy(),
                                 max_new_tokens=5)])
        outs[pc] = [r1[0].tokens, r2[0].tokens]
        if pc:
            assert engine.stats["prefix_cow"] == 1
            assert engine.stats["prefix_hit_tokens"] == 15  # cap: last token
        _assert_drained_leak_free(engine)
    assert outs[True] == outs[False]
    assert outs[True][0] == outs[True][1]


def test_prefix_cache_eviction_under_pressure(setup):
    """A pool too small to retain every finished prompt evicts cached
    blocks LRU instead of refusing admission; every request completes and
    nothing leaks."""
    cfg, params = setup
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, prefill_chunk=8, prefix_cache=True,
                         max_blocks=7)                     # 6 usable blocks
    for i in range(5):
        p = np.random.default_rng(30 + i).integers(0, 256, 16)
        [r] = engine.run([Request(uid=i, prompt=p.astype(np.int32),
                                  max_new_tokens=3)])
        assert r.finish_reason == "length"
    assert engine.stats["prefix_evictions"] > 0
    _assert_drained_leak_free(engine)


def test_prefix_lru_caps_cached_blocks(setup):
    """--prefix-lru bounds the refcount-0 blocks retained after finish."""
    cfg, params = setup
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, prefill_chunk=8, prefix_cache=True,
                         prefix_lru=2)
    for i in range(4):
        p = np.random.default_rng(40 + i).integers(0, 256, 16)
        engine.run([Request(uid=i, prompt=p.astype(np.int32),
                            max_new_tokens=2)])
    assert engine.prefix_index.n_evictable(engine.allocator) <= 2
    _assert_drained_leak_free(engine)


def test_prefix_cache_empty_prompt_and_bad_lru(setup):
    """Regression: an empty prompt must not push the prefill offset
    negative when the prefix cache is on (first_new clamps at 0), and a
    negative prefix_lru is rejected at construction — the engine kwarg
    path must not bypass the ModelConfig validation."""
    cfg, params = setup
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, prefill_chunk=8, prefix_cache=True)
    [r] = engine.run([Request(uid=0, prompt=np.zeros(0, np.int32),
                              max_new_tokens=3)])
    assert r.finish_reason == "length" and len(r.tokens) == 3
    _assert_drained_leak_free(engine)
    with pytest.raises(ValueError, match="prefix_lru"):
        ServeEngine(cfg, params, max_slots=1, max_len=32, paged=True,
                    page_size=8, prefix_cache=True, prefix_lru=-1)


def test_prefix_cache_incapable_configs_serve_cold():
    """Ring-window state is per-slot dense — a prefix hit cannot restore
    it, so local-window configs silently serve cold (hits stay 0) instead
    of erroring or corrupting."""
    cfg = _local_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, prefix_cache=True)
    assert not engine.prefix_cache and not engine.prefix_capable
    prompt = np.random.default_rng(50).integers(0, 256, 16).astype(np.int32)
    engine.run([Request(uid=0, prompt=prompt, max_new_tokens=2)])
    [r] = engine.run([Request(uid=1, prompt=prompt.copy(),
                              max_new_tokens=2)])
    assert r.finish_reason == "length"
    assert engine.stats["prefix_hits"] == 0
    assert engine.allocator.n_free == engine.allocator.capacity


# --------------------------------------------------------------------------
# scheduling: head-of-line fix, truncation, preemption, overlap, streaming
# --------------------------------------------------------------------------
def test_hol_small_request_overtakes_blocked_big_one(setup):
    """Regression for the head-of-line admission stall: a 1-page request
    queued behind a pool-sized one admits immediately under the priority
    policy (skip-with-aging), while fcfs keeps the legacy no-overtaking
    stall. Everything still completes either way."""
    cfg, params = setup
    rng = np.random.default_rng(60)
    mk = lambda: [
        Request(uid=0, prompt=rng0.integers(0, 256, 12).astype(np.int32),
                max_new_tokens=4)                      # 16 tok -> 2 blocks
        for rng0 in [np.random.default_rng(60)]] + [
        Request(uid=1, prompt=np.asarray(
            rng.integers(0, 256, 34), np.int32).copy(),
                max_new_tokens=6),                     # 40 tok -> 5 blocks
        Request(uid=2, prompt=np.asarray(
            rng.integers(0, 256, 4), np.int32).copy(),
                max_new_tokens=2)]                     # 6 tok -> 1 block
    admitted = {}
    for policy in ("priority", "fcfs"):
        reqs = mk()
        engine = ServeEngine(cfg, params, max_slots=2, max_len=64,
                             paged=True, page_size=8, max_blocks=7,
                             sched=policy)
        engine.submit(reqs[0])
        engine.step()                    # uid0 running: 4 of 6 blocks free
        engine.submit(reqs[1])           # needs 5 blocks -> blocked
        engine.submit(reqs[2])           # needs 1 block
        engine.step()
        # uid2 is small enough to admit AND finish within this one step
        admitted[policy] = bool(engine.results[2].tokens)
        steps = 0
        while engine._busy():
            engine.step()
            steps += 1
            assert steps < 2000
        assert all(engine.results[r.uid].finish_reason == "length"
                   for r in reqs), policy
        _assert_drained_leak_free(engine)
    assert admitted["priority"], \
        "small request must overtake the blocked pool-sized one"
    assert not admitted["fcfs"], \
        "fcfs must keep the legacy no-overtaking stall"


def test_aged_reservation_blocks_overtaking(setup):
    """Once aging promotes a blocked request to a reservation, smaller
    late arrivals stop overtaking it (starvation bound)."""
    cfg, params = setup
    rng = np.random.default_rng(61)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, max_blocks=7, sched="priority",
                         sched_aging=2)
    engine.submit(Request(uid=0,
                          prompt=rng.integers(0, 256, 12).astype(np.int32),
                          max_new_tokens=20))          # 2 blocks, long-lived
    engine.step()
    big = Request(uid=1, prompt=rng.integers(0, 256, 34).astype(np.int32),
                  max_new_tokens=6)                    # 5 blocks: blocked
    engine.submit(big)
    engine.step()
    engine.step()                        # two skipped passes -> reserved
    assert engine.scheduler.stats["aged"] == 1
    engine.submit(Request(uid=2,
                          prompt=rng.integers(0, 256, 4).astype(np.int32),
                          max_new_tokens=2))           # would fit, must wait
    engine.step()
    assert 2 not in set(engine.slot_uid[engine.active].tolist()), \
        "a reserved entry must not be overtaken"
    steps = 0
    while engine._busy():
        engine.step()
        steps += 1
        assert steps < 2000
    assert all(engine.results[u].finish_reason == "length" for u in range(3))
    _assert_drained_leak_free(engine)


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_run_max_steps_truncates_leak_free(setup, overlap):
    """Hitting max_steps finishes in-flight slots as 'truncated' (partial
    tokens kept, blocks released) and marks still-queued requests the same
    way — no half-populated Results, no leaked blocks, and the engine keeps
    serving afterwards."""
    cfg, params = setup
    reqs = _mixed_requests(cfg, 5, seed=62, lo=6, hi=14, new_lo=20,
                           new_hi=30)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, prefix_cache=True, overlap=overlap)
    results = engine.run(reqs, max_steps=6)
    assert all(r.finish_reason for r in results), "half-populated Result"
    truncated = [r for r in results if r.finish_reason == "truncated"]
    assert truncated, "max_steps=6 must interrupt these budgets"
    assert any(r.tokens for r in truncated), "partial tokens must be kept"
    assert any("queued" in r.detail for r in truncated), \
        "never-admitted requests get a distinct detail"
    assert not engine.active.any() and engine._pending is None
    assert not engine._admit_hashes, "stale admission hash memo"
    _assert_drained_leak_free(engine)
    [r] = engine.run([Request(uid=99, prompt=np.arange(5, dtype=np.int32),
                              max_new_tokens=3)])
    assert r.finish_reason == "length" and len(r.tokens) == 3
    _assert_drained_leak_free(engine)


def test_preemption_decode_victim_resumes_exact(setup):
    """Under pool pressure a high-priority arrival evicts the youngest
    lower-priority decode; the victim's written pages ride the prefix index
    so resumption is a warm hit, and every request's greedy tokens match
    the unpreempted reference exactly."""
    cfg, params = setup
    rng = np.random.default_rng(63)
    reqs = [Request(uid=i, prompt=rng.integers(0, 256, 12).astype(np.int32),
                    max_new_tokens=6)                  # 3 blocks each
            for i in range(2)]
    hi = Request(uid=2, prompt=rng.integers(0, 256, 8).astype(np.int32),
                 max_new_tokens=4, priority=5)         # 2 blocks
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, max_blocks=7, prefix_cache=True,
                         preemption=True)
    for r in reqs:
        engine.submit(r)
    for _ in range(4):                   # both decoding, pool exhausted
        engine.step()
    assert engine.allocator.n_free == 0
    engine.submit(hi)
    engine.step()
    assert engine.stats["preemptions"] >= 1
    assert 2 in set(engine.slot_uid[engine.active].tolist()), \
        "high-priority request must admit via preemption"
    steps = 0
    while engine._busy():
        engine.step()
        steps += 1
        assert steps < 2000
    for req in reqs + [hi]:
        res = engine.results[req.uid]
        assert res.finish_reason == "length"
        assert res.tokens == _ref_greedy(cfg, params, req.prompt,
                                         req.max_new_tokens,
                                         max_len=64), f"uid {req.uid}"
    assert sum(engine.results[r.uid].preempted for r in reqs) >= 1
    assert engine.stats["prefix_hits"] >= 1, \
        "resumption should re-admit through the prefix index"
    _assert_drained_leak_free(engine)


def test_preemption_mid_prefill_rolls_back(setup):
    """A victim evicted mid-chunk-prefill (pages allocated, nothing
    published yet) rolls back through BlockAllocator.release like a failed
    admission, requeues with its original prompt, and still produces exact
    greedy tokens."""
    cfg, params = setup
    rng = np.random.default_rng(64)
    victim = Request(uid=0, prompt=rng.integers(0, 256, 16).astype(np.int32),
                     max_new_tokens=4)
    hi = Request(uid=1, prompt=rng.integers(0, 256, 8).astype(np.int32),
                 max_new_tokens=3, priority=5)
    engine = ServeEngine(cfg, params, max_slots=1, max_len=64, paged=True,
                         page_size=8, prefill_chunk=4, prefix_cache=True,
                         preemption=True)
    engine.submit(victim)
    engine.step()                        # admitted, first chunk only
    assert engine.phase[0] == 1 and 0 in engine._prefilling, \
        "victim must still be mid-chunk-prefill"
    engine.submit(hi)
    engine.step()
    assert engine.stats["preemptions"] == 1
    assert engine.results[victim.uid].preempted == 1
    steps = 0
    while engine._busy():
        engine.step()
        steps += 1
        assert steps < 2000
    for req in (victim, hi):
        res = engine.results[req.uid]
        assert res.finish_reason == "length"
        assert res.tokens == _ref_greedy(cfg, params, req.prompt,
                                         req.max_new_tokens,
                                         max_len=64), f"uid {req.uid}"
    _assert_drained_leak_free(engine)


@pytest.mark.parametrize("make_cfg", [_cfg, _local_cfg],
                         ids=["global", "local-window"])
def test_overlap_decode_token_parity(make_cfg):
    """Overlapped (double-buffered) stepping is token-identical to the
    synchronous loop on a fixed greedy trace — including a request that
    finishes via eos while step N+1 is already dispatched (its speculative
    overflow token is discarded)."""
    cfg = make_cfg()
    params = init(jax.random.PRNGKey(0), cfg)
    reqs = _mixed_requests(cfg, 6, seed=65, lo=4, hi=16, new_lo=4, new_hi=9)
    # make request 0 finish by eos mid-stream while others keep decoding:
    # its 2nd greedy token becomes the eos id
    ref0 = _ref_greedy(cfg, params, reqs[0].prompt, 3, max_len=64)
    eos = ref0[1]
    outs = {}
    for overlap in (False, True):
        engine = ServeEngine(cfg, params, max_slots=3, max_len=64,
                             paged=True, page_size=8, prefill_chunk=6,
                             eos_id=eos, overlap=overlap)
        results = engine.run([Request(uid=r.uid, prompt=r.prompt,
                                      max_new_tokens=r.max_new_tokens)
                              for r in reqs])
        outs[overlap] = [(r.tokens, r.finish_reason) for r in results]
        assert engine._pending is None
        assert engine.allocator.n_free == engine.allocator.capacity
    assert outs[True] == outs[False]
    assert any(fr == "eos" for _, fr in outs[True]), \
        "trace must include a finish while the next step is dispatched"


def test_overlap_interleaved_with_prefix_cache(setup):
    """Overlap parity holds under randomized submit offsets with prefix
    sharing and COW in play."""
    cfg, params = setup
    rng = np.random.default_rng(66)
    reqs = _shared_prefix_requests(cfg, 7, seed=66)
    submit_at = rng.integers(0, 20, len(reqs))
    outs = {}
    for overlap in (False, True):
        engine = ServeEngine(cfg, params, max_slots=3, max_len=64,
                             paged=True, page_size=8, prefill_chunk=6,
                             prefix_cache=True, overlap=overlap)
        order = sorted(range(len(reqs)), key=lambda i: submit_at[i])
        i = step = 0
        while i < len(order) or engine._busy():
            while i < len(order) and submit_at[order[i]] <= step:
                r = reqs[order[i]]
                engine.submit(Request(uid=r.uid, prompt=r.prompt,
                                      max_new_tokens=r.max_new_tokens))
                i += 1
            engine.step()
            step += 1
            assert step < 5000
        outs[overlap] = [engine.results[r.uid].tokens for r in reqs]
        _assert_drained_leak_free(engine)
    assert outs[True] == outs[False]


def test_streaming_callbacks_and_iterator(setup):
    """Tokens surface incrementally: on_token fires per token in order and
    stream() yields the same sequence the final Result holds, with one
    timestamp per token."""
    cfg, params = setup
    rng = np.random.default_rng(67)
    prompt = rng.integers(0, 256, 8).astype(np.int32)
    ref = _ref_greedy(cfg, params, prompt, 6)
    seen: list[int] = []
    engine = ServeEngine(cfg, params, max_slots=2, max_len=96)
    streamed = list(engine.stream(
        Request(uid=0, prompt=prompt, max_new_tokens=6,
                on_token=lambda t, res: seen.append(t))))
    res = engine.results[0]
    assert streamed == seen == res.tokens == ref
    assert len(res.token_ts) == len(res.tokens)
    assert res.ttft_s is not None and res.ttft_s >= 0
    assert res.token_ts == sorted(res.token_ts)


def test_token_ts_stamped_sync_visible(setup):
    """Pins the timestamp semantics ``Result.token_ts`` / ``ttft_s`` /
    ``itl_s`` are defined by: a token is stamped (and its ``on_token``
    callback fires) when the step's sampled ids become host-visible at
    sync, NOT at dispatch. Under ``overlap`` the next decode step is
    already in flight when token k surfaces, so the callback observes
    ``decode_steps == k + 1`` — except the final token, whose slot had no
    budget left to dispatch (== k). The synchronous loop observes == k."""
    cfg, params = setup
    prompt = np.arange(1, 9, dtype=np.int32)
    for overlap in (False, True):
        engine = ServeEngine(cfg, params, max_slots=1, max_len=64,
                             paged=True, page_size=8, overlap=overlap)
        seen: list[int] = []
        [res] = engine.run([Request(
            uid=0, prompt=prompt, max_new_tokens=5,
            on_token=lambda tok, r, e=engine:
                seen.append(e.stats["decode_steps"]))])
        assert res.finish_reason == "length"
        ts = res.token_ts
        assert len(ts) == 5 == len(seen)
        assert ts == sorted(ts)
        assert res.ttft_s is not None and res.ttft_s > 0
        assert res.itl_s is not None and res.itl_s > 0
        # token 0 comes from prefill (no decode dispatched yet)
        assert seen[0] == 0
        n_dec = len(seen) - 1
        if overlap:
            assert seen[1:] == [k + 1 for k in range(1, n_dec)] + [n_dec], \
                f"overlap stamps must be sync-visible: {seen}"
        else:
            assert seen[1:] == list(range(1, n_dec + 1)), seen


def test_slo_accounting(setup):
    """TTFT SLOs classify finished requests into met/missed goodput
    buckets; requests without SLOs stay unclassified."""
    cfg, params = setup
    rng = np.random.default_rng(68)
    prompt = rng.integers(0, 256, 6).astype(np.int32)
    engine = ServeEngine(cfg, params, max_slots=3, max_len=96)
    res = engine.run([
        Request(uid=0, prompt=prompt, max_new_tokens=3, slo_ttft_ms=1e9),
        Request(uid=1, prompt=prompt.copy(), max_new_tokens=3,
                slo_ttft_ms=1e-6),
        Request(uid=2, prompt=prompt.copy(), max_new_tokens=3),
    ])
    assert res[0].slo_met is True
    assert res[1].slo_met is False
    assert res[2].slo_met is None
    assert engine.stats["slo_met"] == 1 and engine.stats["slo_missed"] == 1


def test_on_device_sampling_temperature(setup):
    """temp > 0 samples on device (fused in the jitted step) and still
    respects budgets; temp == 0 rows stay greedy-deterministic."""
    cfg, params = setup
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, 256, 6).astype(np.int32)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=96, seed=3)
    res = engine.run([
        Request(uid=0, prompt=prompt, max_new_tokens=5, temperature=1.0),
        Request(uid=1, prompt=prompt, max_new_tokens=5, temperature=0.0),
    ])
    assert all(len(r.tokens) == 5 for r in res)
    assert all(0 <= t < cfg.vocab_size for r in res for t in r.tokens)
    assert res[1].tokens == _ref_greedy(cfg, params, prompt, 5)
