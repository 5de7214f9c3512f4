"""Serving benchmark: dense vs paged KV, SPMD scale-out, split pools.

Three sections, all emitting into one ``serve_throughput.csv``:

* **layout** — dense vs paged KV cache on a mixed-length greedy trace:
  identical tokens, paged KV bytes/request drops at mixed lengths, chunked
  prefill compiles ONE shape.
* **scale-out** (``--devices N``) — 1 vs N devices: CPU-only child
  processes with fake devices on CPU, both sizes in this process on chips:
  a KV-head-sharded pool under the same *per-device* HBM budget must admit
  >= 3x the concurrent requests of single-device serving (at N = 4) at
  <= 1.1x the per-device KV bytes per request, with exact greedy parity.
* **split pools** — disaggregated prefill/decode slot pools: the decode
  gap counter (engine steps where queued work exists but no decode was
  dispatched) must not grow with prompt length under ``split_pools``,
  while the unified engine's gap does.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks._util import emit, emit_metrics, run_subprocess

_COLS = ("mode", "layout", "devices", "kv_shard", "split_pools",
         "prompt_len", "requests", "new_tokens", "tok_per_s",
         "kv_bytes_per_request", "kv_bytes_per_request_dev",
         "max_concurrency", "decode_gap_steps", "handoffs",
         "prefill_chunks", "prefill_recompiles", "decode_steps",
         "mfu", "hbm_util")


def _row(**kw) -> dict:
    return {c: kw.get(c, "") for c in _COLS}


def _requests(cfg, n: int, seed: int = 0):
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    return [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    rng.integers(4, 40)).astype(np.int32),
                max_new_tokens=int(rng.integers(4, 12)))
        for i in range(n)
    ]


def _layout_rows() -> list[dict]:
    import jax

    from repro.configs import get_arch, reduced
    from repro.models import init
    from repro.obs import utilization_report
    from repro.serve import Request, ServeEngine

    cfg = reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=512, dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    reqs = _requests(cfg, n=10)
    n_lengths = len({len(r.prompt) for r in reqs})

    rows, tokens = [], {}
    for layout in ("dense", "paged"):
        engine = ServeEngine(cfg, params, max_slots=4, max_len=96,
                             paged=(layout == "paged"), page_size=8,
                             prefill_chunk=16)
        trace = [Request(uid=r.uid, prompt=r.prompt,
                         max_new_tokens=r.max_new_tokens) for r in reqs]
        # counters come off registry snapshots (delta over the run), not
        # hand-differenced stats-dict reads
        snap0 = engine.metrics.snapshot()
        t0 = time.perf_counter()
        results = engine.run(trace)
        dt = time.perf_counter() - t0
        d = engine.metrics.snapshot().delta(snap0)
        util = utilization_report(engine)
        new_tokens = sum(len(r.tokens) for r in results)
        tokens[layout] = [r.tokens for r in results]
        if layout == "paged":
            emit_metrics("serve_throughput", engine,
                         extra={"mode": "layout", "wall_s": round(dt, 3)})
        rows.append(_row(
            mode="layout", layout=layout, devices=1, kv_shard=1,
            split_pools=False, requests=len(results),
            new_tokens=new_tokens, tok_per_s=round(new_tokens / dt, 1),
            kv_bytes_per_request=int(d["kv_bytes_alloc"]) // len(results),
            kv_bytes_per_request_dev=(int(d["kv_bytes_alloc_dev"])
                                      // len(results)),
            max_concurrency=int(d["max_concurrency"]),
            decode_gap_steps=int(d["decode_gap_steps"]),
            handoffs=int(d["handoffs"]),
            prefill_chunks=int(d["prefill_chunks"]),
            prefill_recompiles=int(d["prefill_recompiles"]),
            decode_steps=int(d["decode_steps"]),
            mfu=util["mfu"], hbm_util=util["hbm_util"]))

    dense, paged = rows
    assert tokens["paged"] == tokens["dense"], \
        "paged engine diverged from dense greedy outputs"
    assert paged["kv_bytes_per_request"] < dense["kv_bytes_per_request"], (
        "paged KV bytes/request should drop vs dense at mixed lengths: "
        f"{paged['kv_bytes_per_request']} vs {dense['kv_bytes_per_request']}")
    assert paged["prefill_recompiles"] == 1, (
        "chunked prefill must compile one shape across "
        f"{n_lengths} distinct prompt lengths")
    return rows


def _scale_run(n_dev: int, budget: int) -> dict:
    """One engine over the first ``n_dev`` devices under a per-device KV
    budget: a KV-head-sharded serve mesh when ``n_dev > 1``."""
    import jax

    from repro.configs import get_arch, reduced
    from repro.models import init
    from repro.serve import Request, ServeEngine

    cfg = reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=2, d_model=64, n_heads=8, n_kv_heads=8, head_dim=16,
        d_ff=128, vocab_size=512, dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    part = None
    if n_dev > 1:
        from jax.sharding import Mesh

        from repro.configs.base import StrategyConfig
        from repro.core.sharding import Partitioner
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(1, n_dev),
                    ("data", "model"))
        part = Partitioner(mesh,
                           StrategyConfig(name="ramora", tensor_parallel=True),
                           cfg, mode="serve")
    engine = ServeEngine(cfg, params, max_slots=16, max_len=48, part=part,
                         paged=True, page_size=8, prefill_chunk=16,
                         kv_budget_bytes=budget)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 16).astype(
                        np.int32),
                    max_new_tokens=16) for i in range(20)]
    results = engine.run(reqs)
    assert engine.allocator.n_live == 0, "leaked blocks"
    return {
        "tokens": [r.tokens for r in results],
        "kv_shard": engine._kv_shard,
        "n_blocks": engine.n_blocks,
        "max_concurrency": engine.stats["max_concurrency"],
        "kv_bytes_per_request_dev":
            engine.stats["kv_bytes_alloc_dev"] // len(results),
    }


def _scale_rows(n_dev: int) -> list[dict]:
    import jax

    # the same PER-DEVICE budget on both sides: 16 blocks' worth of a
    # 2-layer K=8 hd=16 fp32 pool (2 * 8rows * 8K * 16hd * 4B = 16 KiB
    # per block) — single-device serving admits 4 concurrent 32-token
    # requests; an N-way KV-head shard holds N x the blocks for the same
    # per-device bytes and admits up to the slot cap
    budget = 16 * 16384
    runs = {}
    for nd in (1, n_dev):
        if jax.default_backend() == "cpu":
            # fake CPU devices: jax fixes the device count at first init,
            # so each count gets a CPU-only child process
            out = run_subprocess(
                "import json\n"
                "from benchmarks.serve_throughput import _scale_run\n"
                f"print(json.dumps(_scale_run({nd}, {budget})))",
                n_devices=max(nd, 1))
            runs[nd] = json.loads(out.strip().splitlines()[-1])
        else:
            # real chips belong to this process: both sizes run here
            runs[nd] = _scale_run(nd, budget)
    base, multi = runs[1], runs[n_dev]
    assert multi["tokens"] == base["tokens"], \
        "sharded serving diverged from single-device greedy outputs"
    assert multi["kv_shard"] == n_dev, (
        f"expected a {n_dev}-way KV shard, got {multi['kv_shard']} "
        "(KV heads must divide the model axis)")
    conc1, concN = base["max_concurrency"], multi["max_concurrency"]
    assert concN >= 3 * conc1, (
        f"scale-out must admit >= 3x the concurrency at the same "
        f"per-device budget: {concN} vs {conc1} x1")
    dev1 = base["kv_bytes_per_request_dev"]
    devN = multi["kv_bytes_per_request_dev"]
    assert devN <= 1.1 * dev1, (
        f"per-device KV bytes/request regressed: {devN} vs {dev1} x1")
    return [_row(mode="scale", layout="paged", devices=nd,
                 kv_shard=runs[nd]["kv_shard"], split_pools=False,
                 prompt_len=16, requests=20,
                 kv_bytes_per_request_dev=runs[nd]
                 ["kv_bytes_per_request_dev"],
                 max_concurrency=runs[nd]["max_concurrency"])
            for nd in (1, n_dev)]


def _gap_rows() -> list[dict]:
    """Unified vs split pools on a long-prefill trace: the decode gap
    (steps with queued work but no decode dispatched) grows with prompt
    length when prefills monopolize unified slots; dedicated decode slots
    keep it flat."""
    import jax

    from repro.configs import get_arch, reduced
    from repro.models import init
    from repro.serve import Request, ServeEngine

    cfg = reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=512, dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)

    def trace(plen: int):
        # two short anchors seed the decode side, then a wave of long
        # prompts whose decode budget outlasts their own prefill
        reqs = [Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, 4).astype(np.int32), max_new_tokens=40)
            for i in range(2)]
        reqs += [Request(uid=10 + i, prompt=rng.integers(
            0, cfg.vocab_size, plen).astype(np.int32), max_new_tokens=24)
            for i in range(8)]
        return reqs

    rows, gaps = [], {}
    for split in (False, True):
        for plen in (32, 128):
            engine = ServeEngine(cfg, params, max_slots=4, max_len=160,
                                 paged=True, page_size=8, prefill_chunk=8,
                                 split_pools=split,
                                 prefill_slots=2 if split else None)
            results = engine.run(trace(plen))
            assert all(r.finish_reason == "length" for r in results)
            gaps[(split, plen)] = engine.stats["decode_gap_steps"]
            rows.append(_row(
                mode="gap", layout="paged", devices=1, kv_shard=1,
                split_pools=split, prompt_len=plen, requests=10,
                max_concurrency=engine.stats["max_concurrency"],
                decode_gap_steps=engine.stats["decode_gap_steps"],
                handoffs=engine.stats["handoffs"],
                decode_steps=engine.stats["decode_steps"]))
    uni_growth = gaps[(False, 128)] - gaps[(False, 32)]
    split_growth = gaps[(True, 128)] - gaps[(True, 32)]
    assert uni_growth > 0, (
        f"unified engine should stall more at longer prompts: "
        f"{gaps[(False, 32)]} -> {gaps[(False, 128)]}")
    assert split_growth <= max(2, uni_growth // 4), (
        f"split-pool decode gap must not grow with prompt length: "
        f"{gaps[(True, 32)]} -> {gaps[(True, 128)]} "
        f"(unified grew {uni_growth})")
    return rows


def _trace_smoke(trace_out: str, metrics_out: str) -> None:
    """CI trace smoke: drive a compact mixed trace — prefix hits, a
    preemption, a COW fork, split-pool handoffs, and a speculative-decode
    turn — with the lifecycle tracer enabled, then validate the exported
    Chrome trace (every admitted request closes its ``request`` span, no
    orphan begin/end pairs) and round-trip the metrics JSON."""
    import jax

    from repro.configs import get_arch, reduced
    from repro.models import init
    from repro.obs import (Snapshot, Tracer, utilization_report,
                           validate_chrome_trace, write_metrics_json)
    from repro.serve import Request, ServeEngine

    cfg = reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")
    params = init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tracer = Tracer(buffer=16384)

    # engine A: prefix sharing + preemption + COW fork under one tight pool
    eng = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                      page_size=8, max_blocks=7, prefix_cache=True,
                      preemption=True, tracer=tracer)
    shared = rng.integers(0, 256, 12).astype(np.int32)
    for uid in (0, 1):       # identical prompts: the second is a warm hit
        eng.submit(Request(uid=uid, prompt=shared.copy(), max_new_tokens=6))
    for _ in range(4):
        eng.step()
    eng.submit(Request(uid=2, prompt=rng.integers(0, 256, 8).astype(np.int32),
                       max_new_tokens=4, priority=5))  # forces a preemption
    steps = 0
    while eng._busy():
        eng.step()
        steps += 1
        assert steps < 5000, "smoke trace failed to drain"
    eng.run([Request(uid=3, prompt=shared.copy(), max_new_tokens=4,
                     temperature=0.8, seed=1, n=2)])   # COW fork
    assert eng.stats["prefix_hits"] >= 1, "no prefix hit in the smoke trace"
    assert eng.stats["preemptions"] >= 1, "no preemption in the smoke trace"
    assert eng.stats["forks"] >= 1, "no fork in the smoke trace"

    # engine B: disaggregated prefill/decode pools (handoff events)
    eng_b = ServeEngine(cfg, params, max_slots=4, max_len=64, paged=True,
                        page_size=8, split_pools=True, prefill_slots=2,
                        tracer=tracer)
    eng_b.run([Request(uid=10 + i,
                       prompt=rng.integers(0, 256, 10).astype(np.int32),
                       max_new_tokens=4) for i in range(3)])
    assert eng_b.stats["handoffs"] >= 1, "no handoff in the smoke trace"

    # engine C: speculative decoding (self-draft: every proposal accepts)
    eng_c = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                        page_size=8, draft_model=cfg, draft_params=params,
                        spec_k=3, tracer=tracer)
    eng_c.run([Request(uid=20,
                       prompt=rng.integers(0, 256, 8).astype(np.int32),
                       max_new_tokens=6)])
    assert eng_c.stats["spec_turns"] >= 1, "no spec turn in the smoke trace"

    tracer.export(trace_out)
    with open(trace_out) as f:
        summary = validate_chrome_trace(json.load(f))
    assert summary["requests"] >= 7, summary   # 5 submitted + 2 fork children

    payload = write_metrics_json(metrics_out, suite="serve_throughput.smoke",
                                 snapshot=eng.metrics.snapshot(),
                                 utilization=utilization_report(eng))
    with open(metrics_out) as f:
        back = json.load(f)
    assert back["schema"] == "repro-metrics-report-v1"
    rt = Snapshot.from_json(json.dumps(back["snapshot"]))
    assert rt == eng.metrics.snapshot(), "metrics JSON round-trip drifted"
    assert payload["utilization"]["steps"] > 0
    print(f"trace smoke OK: {summary['events']} events, "
          f"{summary['requests']} closed request spans, "
          f"{summary['dropped']} dropped -> {trace_out}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="also run the SPMD scale-out comparison: 1 vs N "
                         "devices under the same per-device KV budget "
                         "(fake-device child processes on CPU)")
    ap.add_argument("--dry-run", action="store_true",
                    help="run the mixed-trace tracing smoke (prefix hits, "
                         "preemption, fork, split pools, spec decode) and "
                         "validate the exported trace instead of the full "
                         "benchmark")
    ap.add_argument("--trace-out", default="/tmp/serve_trace.json",
                    help="Chrome-trace output path for --dry-run")
    ap.add_argument("--metrics-out", default="/tmp/serve_metrics.json",
                    help="metrics-report output path for --dry-run")
    # parse_known_args: benchmarks.run invokes suite mains with run.py's own
    # argv still in sys.argv — ignore its flags instead of erroring
    args, _ = ap.parse_known_args(argv)

    if args.dry_run:
        _trace_smoke(args.trace_out, args.metrics_out)
        return

    rows = _layout_rows()
    rows += _gap_rows()
    if args.devices > 1:
        rows += _scale_rows(args.devices)
    emit(rows, "serve_throughput")


if __name__ == "__main__":
    main()
