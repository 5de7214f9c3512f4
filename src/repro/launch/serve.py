"""Serving launcher: continuous-batching engine over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --reduced \
      --requests 12 --slots 4 --max-new 24

``--devices N`` serves SPMD: the paged KV pools shard by KV head over an
N-way model axis (fake CPU devices when no accelerator is attached — the
flag must therefore be handled *before* jax initializes, which is why the
heavy imports live inside :func:`main`). ``--split-pools`` disaggregates
the slot pool into prefill and decode halves (see docs/serving.md).
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _pct_ms(vals, q):
    import numpy as np
    vals = [v for v in vals if v is not None]
    return round(float(np.percentile(vals, q)) * 1e3, 1) if vals else None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kernel-backend", default=None,
                    choices=("auto", "ref", "interpret", "pallas"),
                    help="registry backend for the engine's jitted graphs "
                         "(default: cfg.kernel_backend / XLA paths)")
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="block-pool (paged) KV cache layout "
                         "(default: cfg.paged_kv; --no-paged forces dense)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="KV rows per block (default: cfg.page_size)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill token count (default: "
                         "cfg.prefill_chunk)")
    ap.add_argument("--max-blocks", type=int, default=None,
                    help="global KV block-pool size (default: dense-"
                         "equivalent capacity)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="share fully-written prompt pages across requests "
                         "(refcounted copy-on-write; requires --paged and "
                         "an all-full-attention config; default: "
                         "cfg.prefix_cache)")
    ap.add_argument("--prefix-lru", type=int, default=None,
                    help="max refcount-0 cached blocks retained after "
                         "their owners finish (0 = bounded only by pool "
                         "pressure; default: cfg.prefix_lru)")
    ap.add_argument("--weight-dtype", default=None,
                    choices=("int8", "fp8", "int4"),
                    help="weight-only quantization (repro.quant): wraps "
                         "matmul weights post-load, dispatches gemm_wq "
                         "(int4 packs two nibbles per byte)")
    ap.add_argument("--kv-dtype", default=None, choices=("int8", "fp8"),
                    help="quantized paged KV pools (requires --paged)")
    ap.add_argument("--quant-block", type=int, default=None,
                    help="per-block weight-scale length (0 = per-channel)")
    ap.add_argument("--sched", default=None, choices=("fcfs", "priority"),
                    help="admission policy (default: cfg.sched_policy): "
                         "'priority' = classes + EDF TTFT deadlines + "
                         "fair queuing + skip-with-aging; 'fcfs' = strict "
                         "arrival order")
    ap.add_argument("--sched-aging", type=int, default=None,
                    help="skipped passes before a blocked request reserves "
                         "the pool (0 = never; default: cfg.sched_aging)")
    ap.add_argument("--preemption", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="let a blocked higher-priority request evict a "
                         "lower-priority slot; its pages are kept in the "
                         "prefix index so resumption is a warm hit "
                         "(requires --paged; default: cfg.preemption)")
    ap.add_argument("--overlap", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="double-buffer decode: dispatch step N+1 before "
                         "syncing step N's ids (token-identical; default: "
                         "cfg.overlap_decode)")
    ap.add_argument("--draft-model", default=None,
                    help="registry arch of a smaller draft model: enables "
                         "speculative decoding (requires --paged and an "
                         "all-full-attention config; --reduced applies to "
                         "the draft too)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens proposed per speculative turn "
                         "(default: cfg.spec_k, engine default 4)")
    ap.add_argument("--n", type=int, default=1,
                    help="parallel samples per request: fork the prefilled "
                         "slot into n sequences sharing common KV pages "
                         "copy-on-write (requires --paged)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k sampling filter (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling filter (1.0 = disabled)")
    ap.add_argument("--request-seeds", action="store_true",
                    help="stamp Request.seed = uid on every request: each "
                         "sampling stream becomes reproducible across runs "
                         "and independent of batch composition")
    ap.add_argument("--priority", type=int, default=0,
                    help="priority class stamped on every synthetic "
                         "request (larger = more urgent)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="time-to-first-token SLO target stamped on every "
                         "synthetic request (drives EDF + goodput)")
    ap.add_argument("--slo-itl-ms", type=float, default=None,
                    help="mean inter-token SLO target stamped on every "
                         "synthetic request")
    ap.add_argument("--devices", type=int, default=0,
                    help="serve SPMD over an N-way model axis: the paged "
                         "KV pools shard by KV head (replicated fallback "
                         "when the head count does not divide). Forces N "
                         "fake CPU devices when jax sees fewer real ones "
                         "(0 = single-device serving)")
    ap.add_argument("--split-pools", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="disaggregate the slot pool: dedicated prefill "
                         "slots hand finished prompts to decode slots by "
                         "republishing pool pages (requires --paged; "
                         "default: cfg.split_pools)")
    ap.add_argument("--prefill-slots", type=int, default=None,
                    help="prefill-pool size under --split-pools "
                         "(default: cfg.prefill_slots, 0 = slots // 4)")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine's full metrics snapshot plus the "
                         "utilization report as JSON "
                         "(schema repro-metrics-report-v1)")
    ap.add_argument("--trace-out", default=None,
                    help="enable the request-lifecycle tracer and export "
                         "Chrome-trace JSON here (load in "
                         "https://ui.perfetto.dev)")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="tracer ring-buffer capacity in events; overflow "
                         "drops oldest and is counted in the export")
    args = ap.parse_args(argv)

    if args.devices > 1:
        # must land before jax initializes its backend: fake CPU devices
        # are minted at first import when no accelerator provides enough
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
    import jax
    import numpy as np

    from repro.configs import get_arch, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init as model_init
    from repro.serve import Request, ServeEngine

    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    quant_kw = {k: v for k, v in (("weight_dtype", args.weight_dtype),
                                  ("kv_dtype", args.kv_dtype),
                                  ("quant_block", args.quant_block))
                if v is not None}
    if quant_kw:
        cfg = cfg.replace(**quant_kw)
    params = model_init(jax.random.PRNGKey(args.seed), cfg)
    draft_cfg = draft_params = None
    if args.draft_model:
        draft_cfg = get_arch(args.draft_model)
        if args.reduced:
            draft_cfg = reduced(draft_cfg)
        if draft_cfg.vocab_size != cfg.vocab_size:
            draft_cfg = draft_cfg.replace(vocab_size=cfg.vocab_size)
        draft_params = model_init(jax.random.PRNGKey(args.seed + 1),
                                  draft_cfg)
    part = None
    if args.devices > 1:
        from repro.configs.base import StrategyConfig
        from repro.core.sharding import Partitioner
        if len(jax.devices()) < args.devices:
            raise SystemExit(
                f"--devices {args.devices} but jax sees "
                f"{len(jax.devices())} (XLA_FLAGS was set too late?)")
        mesh = jax.make_mesh((1, args.devices), ("data", "model"))
        part = Partitioner(mesh,
                           StrategyConfig(name="ramora",
                                          tensor_parallel=True),
                           cfg, mode="serve")
    tracer = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer(buffer=args.trace_buffer)
    engine = ServeEngine(cfg, params, max_slots=args.slots, tracer=tracer,
                         max_len=args.max_len, seed=args.seed, part=part,
                         kernel_backend=args.kernel_backend,
                         paged=args.paged, page_size=args.page_size,
                         prefill_chunk=args.prefill_chunk,
                         max_blocks=args.max_blocks,
                         prefix_cache=args.prefix_cache,
                         prefix_lru=args.prefix_lru,
                         sched=args.sched, sched_aging=args.sched_aging,
                         preemption=args.preemption, overlap=args.overlap,
                         draft_model=draft_cfg, draft_params=draft_params,
                         spec_k=args.spec_k, split_pools=args.split_pools,
                         prefill_slots=args.prefill_slots)

    rng = np.random.default_rng(args.seed)
    reqs = []
    for uid in range(args.requests):
        plen = int(rng.integers(4, 32))
        prompt = rng.integers(0, cfg.vocab_size, size=plen).astype(np.int32)
        frames = extra = None
        if cfg.frontend == "audio":
            frames = rng.standard_normal(
                (cfg.encoder.n_frames, cfg.d_model)).astype(np.float32)
        if cfg.frontend == "vision":
            extra = rng.standard_normal(
                (cfg.n_frontend_tokens, cfg.d_model)).astype(np.float32)
        reqs.append(Request(uid=uid, prompt=prompt,
                            max_new_tokens=args.max_new,
                            temperature=args.temperature,
                            top_k=args.top_k, top_p=args.top_p, n=args.n,
                            seed=uid if args.request_seeds else None,
                            frames=frames, extra_embeds=extra,
                            priority=args.priority,
                            slo_ttft_ms=args.slo_ttft_ms,
                            slo_itl_ms=args.slo_itl_ms))

    t0 = time.time()
    results = engine.run(reqs)
    dt = time.time() - t0
    new_tokens = sum(len(r.tokens) for r in results)
    new_tokens += sum(len(c.tokens) for r in results for c in r.children)
    # analytic d2d floor: per-device interconnect seconds per decode step
    # under the KV-head shard (zeros on one device / replicated pools)
    from repro.core.memfloor import d2d_bytes_serve_decode
    from repro.core.topology import CHIP
    from repro.obs import utilization_report, write_metrics_json
    d2d = d2d_bytes_serve_decode(cfg, engine.max_slots, engine._kv_shard)
    # measured-window utilization: MFU + bandwidth fractions joined from
    # the engine's decode_window_* metrics and the memfloor model
    util = utilization_report(engine)
    if args.metrics_out:
        write_metrics_json(args.metrics_out, suite="launch.serve",
                           snapshot=engine.metrics.snapshot(),
                           utilization=util,
                           extra={"arch": cfg.name,
                                  "requests": len(results),
                                  "wall_s": round(dt, 3)})
    if tracer is not None:
        tracer.export(args.trace_out)
    print(json.dumps({
        "arch": cfg.name, "requests": len(results),
        "completed": sum(1 for r in results if r.finish_reason),
        "new_tokens": new_tokens, "wall_s": round(dt, 2),
        "tok_per_s": round(new_tokens / dt, 1),
        "decode_steps": engine.stats["decode_steps"],
        "prefill_chunks": engine.stats["prefill_chunks"],
        "prefill_recompiles": engine.stats["prefill_recompiles"],
        "paged": engine.paged,
        "prefix_cache": engine.prefix_cache,
        "prefix_hits": engine.stats["prefix_hits"],
        "prefix_hit_tokens": engine.stats["prefix_hit_tokens"],
        "prefix_cow": engine.stats["prefix_cow"],
        "kv_bytes_cached": engine.stats["kv_bytes_cached"],
        "kv_bytes_per_request": (engine.stats["kv_bytes_alloc"]
                                 // max(len(results), 1)),
        "devices": args.devices or 1,
        "kv_shard": engine._kv_shard,
        # divisibility drops (e.g. KV heads not dividing the model axis)
        # replicate silently inside the Partitioner — surface them here so
        # a misconfigured mesh is visible in the run record
        "dropped_axes": (part.dropped if part is not None else []),
        "kv_bytes_per_request_dev": (engine.stats["kv_bytes_alloc_dev"]
                                     // max(len(results), 1)),
        "d2d_bytes_per_step_dev": round(d2d["total"], 1),
        "d2d_s_floor_per_step": d2d["total"] / CHIP.ici_link_bw,
        "utilization": util,
        "split_pools": engine.split_pools,
        "prefill_slots": engine.prefill_slots,
        "handoffs": engine.stats["handoffs"],
        "handoff_wait_steps": engine.stats["handoff_wait_steps"],
        "decode_gap_steps": engine.stats["decode_gap_steps"],
        "max_concurrency": engine.stats["max_concurrency"],
        "sched": engine.scheduler.policy,
        "sched_skips": engine.stats["sched_skips"],
        "sched_requeues": engine.scheduler.stats["requeues"],
        "preemptions": engine.stats["preemptions"],
        "spec_k": engine.spec_k if engine.draft is not None else None,
        "spec_turns": engine.stats["spec_turns"],
        "spec_accept_rate": (round(engine.stats["spec_accepted"]
                                   / max(engine.stats["spec_proposed"], 1),
                                   3)
                             if engine.draft is not None else None),
        "forks": engine.stats["forks"],
        "fork_shared_blocks": engine.stats["fork_shared_blocks"],
        "ttft_p50_ms": _pct_ms([r.ttft_s for r in results], 50),
        "ttft_p99_ms": _pct_ms([r.ttft_s for r in results], 99),
        "goodput": (round(engine.stats["slo_met"]
                          / max(engine.stats["slo_met"]
                                + engine.stats["slo_missed"], 1), 3)
                    if args.slo_ttft_ms is not None
                    or args.slo_itl_ms is not None else None),
    }, indent=1))
    assert all(r.finish_reason for r in results), "unfinished requests"


if __name__ == "__main__":
    main()
