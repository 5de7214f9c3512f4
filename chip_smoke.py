#!/usr/bin/env python3
"""Chip smoke test: serve Qwen3-0.6B at full published width on a TPU.

    python3 chip_smoke.py [--seed 0]   # one chip
    python3 chip_smoke.py --chips 4     # serve-mode SPMD only

One chip (the default) runs two phases, each of which must pass:

1. **Kernel parity.** At Qwen3-0.6B width, the compiled ``paged_attention``
   (bf16 and int8 KV), ``gemm`` and ``gemm_wq`` (int8, int4) run on the chip
   against the ``ref`` oracle on the same inputs. Tolerance, elementwise:
   ``|kernel - ref| <= 0.02 + 0.02 * |ref|`` (bf16 inputs, fp32 reference).
2. **Serving.** ``ServeEngine`` with the paged KV cache (page 16), chunked
   prefill, 8 slots, ``max_len`` 2048 and the kernel backend on platform
   auto serves 12 requests of mixed prompt length, 32 to 64 new tokens
   each. Every request must finish with the tokens it asked for, the block
   allocator must drain (``n_live == 0``), and the registry's record must
   show ``paged_attention`` and ``gemm`` ran as Pallas kernels, never as
   ``ref``.

``--chips 4`` runs only the path that exists across chips: the same model
served by a ``Partitioner(mode="serve")`` over a ``(1, 4)`` mesh of the four
TPU devices (KV pools sharded by KV head), and the same requests on one of
those chips in the same process. It checks the pools are sharded four ways,
compares first-step logits (relative L2 error <= 0.05) and reports greedy
token agreement.

Weights are random, made from ``--seed``; nothing is downloaded. Times and
memory are printed as information, not as benchmark numbers. The last line
of standard output is ``{"ok": true, "device": {...}}``. The script exits
non-zero, printing no such line, when JAX finds no TPU or any check fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen3-0.6b"
MAX_SLOTS, MAX_LEN, PAGE, CHUNK = 8, 2048, 16, 64
#: kernel-vs-ref parity: |kernel - ref| <= ATOL + RTOL * |ref|
ATOL = RTOL = 0.02
#: four-chip vs one-chip first-step logits: ||a - b||_2 / ||b||_2. bf16
#: rounds at 2^-8 per op through 28 residual layers; a sharding fault (a
#: head read from the wrong shard) moves logits by O(1)
LOGITS_REL_L2 = 0.05


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------------
# phase 1: kernel parity at full width
# --------------------------------------------------------------------------
def _close(name: str, got, want) -> None:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite kernel output")
    err = np.abs(got - want)
    bound = ATOL + RTOL * np.abs(want)
    log(f"parity {name}: max|kernel-ref| {float(err.max())!r} "
        f"(worst err/bound {float((err / bound).max())!r})")
    check(bool((err <= bound).all()),
          f"{name}: kernel differs from ref beyond {ATOL} + {RTOL}*|ref|")


def kernel_parity(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, use_backend
    from repro.kernels.dispatch import registry
    from repro.quant import quantize_kv, quantize_tensor

    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 8)
    B, K, D = MAX_SLOTS, cfg.n_kv_heads, cfg.resolved_head_dim
    G = cfg.n_heads // K
    P = MAX_LEN // PAGE
    N = B * P + 1
    q = jax.random.normal(ks[0], (B, K, G, D), jnp.float32).astype(
        jnp.bfloat16)
    kp = jax.random.normal(ks[1], (N, PAGE, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (N, PAGE, K, D), jnp.float32)
    # every slot owns distinct pool blocks, in a shuffled order
    tables = (jnp.asarray(np.random.default_rng(seed).permutation(N - 1)[
        :B * P].reshape(B, P)) + 1).astype(jnp.int32)
    # mixed lengths from one token to a full table
    lengths = jnp.asarray(np.linspace(1, MAX_LEN, B).astype(np.int32))
    kq, kscale = quantize_kv(kp, "int8")
    vq, vscale = quantize_kv(vp, "int8")
    kb, vb = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)

    d, f = cfg.d_model, cfg.d_ff
    x = jax.random.normal(ks[3], (64, d), jnp.float32).astype(jnp.bfloat16)
    w_up = (jax.random.normal(ks[4], (d, f), jnp.float32)
            / d ** 0.5).astype(jnp.bfloat16)
    w_dn = jax.random.normal(ks[5], (f, d), jnp.float32) / f ** 0.5
    h = jax.random.normal(ks[6], (8, f), jnp.float32).astype(jnp.bfloat16)
    q8 = quantize_tensor(w_dn, "int8", block=128)
    q4 = quantize_tensor(w_dn, "int4", block=128)

    cases = {
        "paged_attention[bf16 kv]": (ops.paged_attention,
                                     (q, kb, vb, tables, lengths), {}),
        "paged_attention[int8 kv]": (ops.paged_attention,
                                     (q, kq, vq, tables, lengths, kscale,
                                      vscale), {}),
        "gemm[d_model x d_ff, silu]": (ops.gemm, (x, w_up), {"act": "silu"}),
        "gemm[d_ff x d_model]": (ops.gemm, (h, w_dn.astype(jnp.bfloat16)),
                                 {}),
        "gemm_wq[int8]": (ops.gemm_wq, (h, q8.q, q8.scales), {}),
        "gemm_wq[int4]": (ops.gemm_wq, (h, q4.q, q4.scales), {}),
    }
    for name, (fn, args, kw) in cases.items():
        op = name.split("[")[0]
        registry.clear_resolutions()
        with use_backend("pallas"):
            got = jax.block_until_ready(fn(*args, **kw))
        impls = registry.resolutions()
        check(impls == {(op, "pallas"): 1},
              f"{name}: expected the Pallas kernel, registry chose {impls}")
        with use_backend("ref"):
            want = fn(*args, **kw)
        _close(name, got, want)


# --------------------------------------------------------------------------
# phase 2: serving through the engine
# --------------------------------------------------------------------------
def make_requests(cfg, seed: int, n: int = 12):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    lens = rng.integers(8, MAX_LEN * 3 // 10, n)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, int(lens[i])
                                        ).astype(np.int32),
                    max_new_tokens=int(rng.integers(32, 65)))
            for i in range(n)]


def make_engine(cfg, params, *, part=None):
    from repro.serve import ServeEngine

    return ServeEngine(cfg, params, max_slots=MAX_SLOTS, max_len=MAX_LEN,
                       paged=True, page_size=PAGE, prefill_chunk=CHUNK,
                       part=part, kernel_backend="auto")


def serve(engine, reqs, label: str) -> list:
    """Run ``reqs`` to completion and check every result and the pool."""
    import jax

    t0 = time.perf_counter()
    results = engine.run(reqs)
    wall = time.perf_counter() - t0
    want = {r.uid: r.max_new_tokens for r in reqs}
    check(len(results) == len(reqs),
          f"{label}: {len(results)} results for {len(reqs)} requests")
    vocab = engine.cfg.vocab_size
    for r in results:
        check(r.finish_reason in ("length", "eos"),
              f"{label}: request {r.uid} finished {r.finish_reason!r} "
              f"({r.detail})")
        check(len(r.tokens) == want[r.uid],
              f"{label}: request {r.uid} got {len(r.tokens)} tokens, "
              f"asked for {want[r.uid]}")
        check(all(0 <= t < vocab for t in r.tokens),
              f"{label}: request {r.uid} emitted a token outside the vocab")
    check(engine.allocator.n_live == 0,
          f"{label}: allocator holds {engine.allocator.n_live} live blocks")
    n_tok = sum(len(r.tokens) for r in results)
    mem = jax.devices()[0].memory_stats() or {}
    log(f"{label}: wall time {wall!r} s including compilation, "
        f"{n_tok} new tokens, {n_tok / wall!r} tokens/s, "
        f"decode steps {engine.stats['decode_steps']}, "
        f"prefill chunks {engine.stats['prefill_chunks']}, "
        f"device 0 peak bytes in use {mem.get('peak_bytes_in_use')}")
    return results


def serve_one_chip(cfg, params, seed: int) -> None:
    from repro.kernels.dispatch import registry

    registry.clear_resolutions()
    engine = make_engine(cfg, params)
    serve(engine, make_requests(cfg, seed), "serve[1 chip]")
    impls = registry.resolutions()
    log(f"registry resolutions: "
        f"{ {f'{op}/{impl}': n for (op, impl), n in sorted(impls.items())} }")
    for op in ("paged_attention", "gemm"):
        check(impls.get((op, "pallas"), 0) > 0,
              f"{op} never ran as the Pallas kernel: {impls}")
        check((op, "ref") not in impls,
              f"{op} fell back to the ref oracle: {impls}")


# --------------------------------------------------------------------------
# four chips: serve-mode SPMD against one chip
# --------------------------------------------------------------------------
def first_step_logits(cfg, params, prompt, part=None):
    """Prefill ``prompt`` into slot 0 of a fresh paged cache laid out as the
    engine lays it out (KV-head-sharded pools under ``part``), then decode
    one greedy token. Returns (prefill logits, decode logits)."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.kernels import use_backend
    from repro.models import decode_step, extend_step
    from repro.models.cache import init_cache, pages_per_slot

    n_pages = pages_per_slot(MAX_LEN, PAGE)
    n_blocks = n_pages + 1
    cache = init_cache(cfg, 1, MAX_LEN, n_blocks=n_blocks, page_size=PAGE)
    if part is not None:
        cache = jax.device_put(cache,
                               part.serve_cache_sharding(cache, n_blocks))
        params = jax.device_put(params, part.params_sharding(params))
    tables = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    T = len(prompt)
    scope = (use_backend("auto") if part is None
             else contextlib.nullcontext())
    with scope:
        prefill = jax.jit(lambda p, c, t: extend_step(
            p, cfg, c, t, jnp.int32(0), jnp.int32(T), jnp.int32(0),
            block_tables=tables, part=part))
        lp, cache = prefill(params, cache, jnp.asarray(prompt)[None])
        tok = jnp.argmax(lp[:, 0], axis=-1).astype(jnp.int32)[:, None]
        decode = jax.jit(lambda p, c, t: decode_step(
            p, cfg, c, t, jnp.full((1,), T, jnp.int32), part=part,
            active=jnp.ones((1,), bool), block_tables=tables))
        ld, _ = decode(params, cache, tok)
    return lp[0, 0], ld[0, 0]


def _rel_l2(a, b) -> float:
    import numpy as np

    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def four_chips(cfg, params, seed: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import StrategyConfig
    from repro.core.sharding import Partitioner

    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX sees "
                             f"{len(devices)}")
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 4), ("data", "model"))
    part = Partitioner(mesh, StrategyConfig(name="ramora",
                                            tensor_parallel=True),
                       cfg, mode="serve")
    reqs = make_requests(cfg, seed)

    # first-step logits: sharded pools against one chip
    prompt = reqs[0].prompt
    lp1, ld1 = first_step_logits(cfg, params, prompt)
    lp4, ld4 = first_step_logits(cfg, params, prompt, part=part)
    for name, a, b in (("prefill", lp4, lp1), ("decode", ld4, ld1)):
        err = _rel_l2(a, b)
        same = int(np.argmax(np.asarray(a))) == int(np.argmax(np.asarray(b)))
        log(f"first-step {name} logits, 4 chips vs 1: relative L2 "
            f"{err!r}, argmax agrees {same}")
        check(bool(np.isfinite(np.asarray(a)).all()),
              f"{name} logits not finite on 4 chips")
        check(err <= LOGITS_REL_L2,
              f"{name} logits differ: relative L2 {err} > {LOGITS_REL_L2}")

    one = serve(make_engine(cfg, params), reqs, "serve[1 chip]")
    eng4 = make_engine(cfg, params, part=part)
    check(eng4._kv_shard == 4, f"kv_shard is {eng4._kv_shard}, not 4")
    # K/V pools: (..., n_blocks, page, K, D), a leading layer axis when the
    # layers are scanned; KV heads are the second-minor axis
    pools = [leaf for leaf in jax.tree.leaves(eng4.cache)
             if leaf.ndim >= 4 and eng4.n_blocks in leaf.shape[:2]]
    check(bool(pools), "no KV pool leaves in the 4-chip cache")
    for leaf in pools:
        shards = leaf.addressable_shards
        devs = {s.device for s in shards}
        check(len(devs) == 4, f"pool on {len(devs)} devices, not 4")
        check(all(s.data.shape[-2] == leaf.shape[-2] // 4 for s in shards),
              f"pool shards {[s.data.shape for s in shards]} do not split "
              f"{leaf.shape[-2]} KV heads 4 ways")
    log(f"KV pools: {len(pools)} leaves of shape {pools[0].shape}, each on "
        f"4 devices with {pools[0].shape[-2] // 4} KV heads per shard")
    four = serve(eng4, make_requests(cfg, seed), "serve[4 chips]")
    by_uid = {r.uid: r.tokens for r in one}
    agree = [float(np.mean(np.asarray(r.tokens) == np.asarray(by_uid[r.uid])))
             for r in four]
    exact = sum(r.tokens == by_uid[r.uid] for r in four)
    log(f"greedy tokens, 4 chips vs 1: {exact}/{len(four)} requests "
        f"identical, mean positionwise agreement {float(np.mean(agree))!r}")


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: serve-mode SPMD over four chips against one "
                         "chip, and nothing else")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (first device: {dev.platform} "
              f"{dev.device_kind}); nothing runs on another platform",
              file=sys.stderr)
        return 1

    from repro.configs import get_arch
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_arch(ARCH)
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads, head_dim "
        f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"{len(jax.devices())} x {dev.device_kind}")
    params = init(jax.random.PRNGKey(args.seed), cfg)
    try:
        if args.chips == 4:
            four_chips(cfg, params, args.seed)
        else:
            kernel_parity(cfg, args.seed)
            serve_one_chip(cfg, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
