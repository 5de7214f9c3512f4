"""Serving engine: continuous batching over a paged (block-pool) KV cache.

The engine owns a fixed pool of ``max_slots`` sequence slots sharing one
batched KV/recurrent cache. Two cache layouts:

* **dense** — every slot statically reserves ``max_len`` KV rows.
* **paged** (``paged=True`` / ``cfg.paged_kv``) — full-attention KV lives in
  a global pool of ``page_size``-row blocks handed out by a
  :class:`BlockAllocator`; admission is gated on free *blocks* for the
  request's ``len(prompt) + max_new_tokens`` tokens, so KV memory tracks
  actual sequence lengths instead of ``max_slots × max_len``. This is the
  serving analogue of Occamy's banked-TCDM + ROB-less NI memory story: many
  independent in-flight streams over fixed-size blocks, no per-stream
  worst-case reservation. Blocks free the moment a request finishes.

With ``prefix_cache=True`` (``cfg.prefix_cache``), fully-written prompt
pages are published into a :class:`repro.serve.prefix.PrefixIndex` (page-
granular chain hashes); a later request whose prompt shares the prefix maps
those blocks read-only into its block table (refcount++), skips prefill for
the matched pages, and chunk-prefills only the tail from ``first_new_pos``.
Writes to a shared block privatize it first (copy-on-write: fresh block,
jitted page copy, table remap). Finished requests leave their indexed pages
resident as refcount-0 *cached* blocks, reclaimed LRU under pool pressure —
so a hot system prompt's KV survives between requests at zero steady-state
cost. All-full-attention configs only (ring/recurrent per-slot state cannot
be restored from the pool); incapable configs serve cold.

Admission order is owned by a :class:`repro.serve.scheduler.Scheduler`
policy layer: priority classes, per-request SLO deadlines (TTFT targets go
earliest-deadline-first once urgent), multi-tenant fair queuing over
``Request.user``, and — the head-of-line fix — *skip-with-aging*: a request
blocked on pool resources is skipped in favor of smaller ones that fit now,
until aging promotes it to a reservation nothing may overtake. With
``preemption=True`` a high-priority arrival that cannot get blocks evicts a
lower-priority victim: the victim's fully-written pages are published into
the prefix index (when enabled), its blocks released through the refcount
path, and the request requeued with its generated tokens folded into the
prompt — resumption chunk-prefills only the un-cached tail via
``first_new_pos``, so preemption costs a warm prefix hit, not a byte swap.

Prefill is **chunked**: prompts advance ``prefill_chunk`` tokens per engine
step through one jitted ``extend_step`` graph (ragged tails ride in the same
shape behind an ``n_valid`` scalar), interleaved with decode steps for the
already-running slots — one compiled prefill shape regardless of prompt
length, and no prefill head-of-line blocking of the decode pool. Enc-dec
(audio) and vlm requests, and SPMD serving (``part``), keep the legacy
whole-prompt prefill path (jit per distinct length).

Sampling is fused into the jitted step (per-slot temperatures + PRNG key as
inputs): each ``step()`` syncs only the sampled token ids to host, never the
``(max_slots, vocab)`` logits. Cache buffers are donated through every
jitted update, so admission/decode cost scales with the written region, not
the pool. With ``overlap=True`` the decode loop double-buffers: step N+1 is
dispatched on device (fed step N's sampled ids *as a device array*) before
step N's ids are synced to host, so host bookkeeping and admission overlap
device compute — token streams are identical, ids just reach callbacks one
step later.

Tokens stream out as they are sampled: every append stamps a
``perf_counter`` timestamp into ``Result.token_ts`` and fires the request's
``on_token`` callback; :meth:`ServeEngine.stream` wraps submit+step into a
per-request iterator.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field, replace as dc_replace
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import dispatch as kdispatch
from repro.models import decode_step, extend_step, forward, logits_fn, \
    verify_step
from repro.models.cache import copy_block, default_n_blocks, init_cache, \
    kv_bytes, n_blocks_for_bytes, pages_per_slot
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.quant import is_quant_dtype, quantize_params
from repro.serve.prefix import PrefixIndex, page_hashes
from repro.serve.scheduler import Scheduler
from repro.spec import DraftWorker, sample_tokens, speculative_accept
from repro.spec.sampling import (P_ACCEPT as _P_ACCEPT,
                                 P_FORK as _P_FORK,
                                 P_SAMPLE as _P_SAMPLE,
                                 fold_keys as _fold_keys)

PyTree = Any

#: Slot lifecycle: FREE -> PREFILL (chunked) -> DECODE -> FREE.
FREE, PREFILL, DECODE = 0, 1, 2


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                      # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0                # 0 => greedy
    top_k: int = 0                          # 0 => no top-k filter
    top_p: float = 1.0                      # >= 1 => no nucleus filter
    #: parallel sampling: fork the prefilled slot into n sequences that
    #: share all common KV pages copy-on-write (paged all-full configs);
    #: children land on ``Result.children``
    n: int = 1
    #: per-request PRNG seed: the sampling stream depends only on
    #: (seed, step) — not on pool co-residents or admission order
    seed: int | None = None
    frames: np.ndarray | None = None        # enc-dec (audio) models
    extra_embeds: np.ndarray | None = None  # vlm models
    # scheduling (repro.serve.scheduler)
    priority: int = 0                       # larger = more urgent
    user: str | None = None                 # tenant for fair queuing
    slo_ttft_ms: float | None = None        # time-to-first-token target
    slo_itl_ms: float | None = None         # mean inter-token target
    #: streaming callback, called as ``on_token(token, result)`` the moment
    #: each token reaches the host (with overlap, one step after sampling)
    on_token: Callable[[int, "Result"], None] | None = None


@dataclass
class Result:
    uid: int
    tokens: list[int] = field(default_factory=list)
    finish_reason: str = ""                 # eos | length | rejected | truncated
    detail: str = ""                        # rejection/truncation cause
    prefill_s: float = 0.0
    decode_steps: int = 0
    submit_s: float = 0.0                   # perf_counter at submit
    token_ts: list[float] = field(default_factory=list)  # one per token
    preempted: int = 0                      # times evicted and requeued
    slo_met: bool | None = None             # None = request had no SLO
    #: parallel sampling (``Request.n > 1``): one Result per forked child,
    #: in fork order — the parent's own tokens stay on this Result
    children: list["Result"] = field(default_factory=list)

    @property
    def ttft_s(self) -> float | None:
        """Submit-to-first-token latency (queueing + prefill)."""
        return (self.token_ts[0] - self.submit_s) if self.token_ts else None

    @property
    def itl_s(self) -> float | None:
        """Mean inter-token latency over the decoded tokens."""
        if len(self.token_ts) < 2:
            return None
        return (self.token_ts[-1] - self.token_ts[0]) / (len(self.token_ts) - 1)


class BlockAllocator:
    """Refcounted free-list allocator over the global KV block pool.

    Block 0 is the *null block*: never handed out, it absorbs the dropped
    writes of inactive slots and ragged prefill tails (their scatter indices
    route out of bounds / to the null entry instead of another stream's
    data — the block-pool equivalent of writing into a scratch bank).

    Every other block is in exactly one of three states:

    * **free** — on the free list, refcount 0;
    * **live** — refcount >= 1: owned by one slot, or *shared* read-only by
      several slots through the prefix cache (``incref`` per sharer; a write
      to a shared block must copy-on-write first);
    * **cached** — refcount 0 but pinned by the :class:`PrefixIndex`
      (``evictor``): retained after its last owner finished so future
      prefix hits can adopt it, evictable LRU under pool pressure.

    ``alloc`` is transactional: if the grant cannot be completed — even
    after asking the evictor to reclaim cached blocks — every block already
    popped is rolled back onto the free list before the error propagates,
    so a partial failure never leaks blocks.
    """

    def __init__(self, n_blocks: int, page_size: int, n_shards: int = 1,
                 metrics=None):
        from repro.obs.metrics import MetricsRegistry
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # conservation invariant (tests/test_allocator_props.py):
        # blocks_granted - blocks_released == n_live + cached
        self._c_granted = self.metrics.counter(
            "blocks_granted", "blocks removed from the free list by alloc()")
        self._c_released = self.metrics.counter(
            "blocks_released", "blocks returned to the free list")
        self._c_adopted = self.metrics.counter(
            "blocks_adopted", "cached blocks revived to live by incref()")
        self.n_blocks = n_blocks
        self.page_size = page_size
        #: mesh shards the pool tensors are split over (serve-mode KV-head
        #: sharding). Block ids are *global*: every shard holds rows
        #: ``1/n_shards`` of each block, so one grant is implicitly a
        #: transaction of ``n_shards`` per-shard sub-grants that commit and
        #: roll back atomically — the single free list IS the cross-shard
        #: transaction log, and budgets are per-shard by construction
        #: (every device pays ``block_bytes / n_shards`` per granted block).
        self.n_shards = max(1, n_shards)
        self._free = list(range(n_blocks - 1, 0, -1))
        self.ref = np.zeros(n_blocks, np.int32)
        self.evictor = None      # PrefixIndex (or None): reclaims cached

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        """Distinct blocks with refcount >= 1."""
        return int((self.ref > 0).sum())

    @property
    def n_evictable(self) -> int:
        return 0 if self.evictor is None else self.evictor.n_evictable(self)

    @property
    def n_available(self) -> int:
        """Blocks an ``alloc`` could obtain right now (free + evictable)."""
        return self.n_free + self.n_evictable

    @property
    def capacity(self) -> int:
        return self.n_blocks - 1

    def pages_for(self, n_tokens: int) -> int:
        return pages_per_slot(n_tokens, self.page_size)

    def alloc(self, n: int) -> list[int]:
        """Grant ``n`` fresh blocks at refcount 1, evicting cached blocks
        as needed; rolls the partial grant back cleanly on failure."""
        got: list[int] = []
        try:
            for _ in range(n):
                if not self._free and self.evictor is not None:
                    self.evictor.evict_one(self)
                if not self._free:
                    raise RuntimeError(
                        f"allocator exhausted: want {n}, free {self.n_free} "
                        f"(+{self.n_evictable} evictable)")
                blk = self._free.pop()
                if self.ref[blk] != 0:      # corrupted free list
                    self._free.append(blk)
                    raise RuntimeError(f"free-list block {blk} has "
                                       f"refcount {int(self.ref[blk])}")
                self.ref[blk] = 1
                got.append(blk)
        except Exception:
            for blk in reversed(got):
                self.ref[blk] = 0
                self._free.append(blk)
            raise
        self._c_granted.inc(len(got))
        return got

    def incref(self, block: int) -> None:
        """Adopt a cached block (0 -> 1) or add a sharer to a live one."""
        if not 0 < block < self.n_blocks:
            raise ValueError(f"invalid block id {block}")
        if (self.evictor is not None and self.ref[block] == 0
                and self.evictor.is_cached(block)):
            self.evictor.note_adopted(block)     # cached -> live
            self._c_adopted.inc()
        self.ref[block] += 1

    def decref(self, block: int, *, retain: bool = False) -> int:
        """Drop one reference. At refcount 0 the block returns to the free
        list unless ``retain`` (the prefix index keeps it cached). Returns
        the new refcount; a double free raises instead of corrupting."""
        r = int(self.ref[block]) - 1
        if r < 0:
            raise RuntimeError(f"double free of block {block}")
        self.ref[block] = r
        if r == 0:
            if not retain:
                self._free.append(block)
                self._c_released.inc()
            elif self.evictor is not None:
                self.evictor.note_cached(block)  # live -> cached
        return r

    def free_block(self, block: int) -> None:
        """Return an (evicted, refcount-0) block to the free list."""
        if self.ref[block] != 0:
            raise RuntimeError(f"freeing live block {block} "
                               f"(refcount {int(self.ref[block])})")
        self._free.append(block)
        self._c_released.inc()

    def release(self, blocks: list[int]) -> None:
        """Drop one reference on each block; blocks pinned by the evictor
        (prefix index) are retained as cached instead of freed."""
        for blk in blocks:
            retain = (self.evictor is not None
                      and self.evictor.is_cached(blk))
            self.decref(blk, retain=retain)


def _sample(logits, temps, top_k, top_p, keys):
    """Fused on-device sampler: greedy rows where temp <= 0, top-k/top-p
    filtered temperature sampling otherwise, one PRNG key per row. Runs
    inside the jitted step: only sampled ids reach the host."""
    return sample_tokens(logits, temps, top_k, top_p, keys)


def _h2d(x):
    """A device array of host state that owns its bytes. On the CPU
    ``jnp.asarray`` and ``jnp.array`` may alias a NumPy buffer, and the
    engine mutates its slot arrays and block tables in place while a step
    it dispatched asynchronously may not have read them yet."""
    return jnp.asarray(np.array(x, copy=True))


@dataclass
class _Pending:
    """One dispatched-but-unsynced decode step (overlap double-buffer)."""
    ids: Any                 # (max_slots,) int32 device array
    mask: np.ndarray         # slots this dispatch decoded
    uids: np.ndarray         # slot -> uid snapshot at dispatch time


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params: PyTree, *, max_slots: int = 4,
                 max_len: int = 512, eos_id: int | None = None, seed: int = 0,
                 part=None, kernel_backend: str | None = None,
                 paged: bool | None = None, page_size: int | None = None,
                 prefill_chunk: int | None = None,
                 max_blocks: int | None = None,
                 kv_budget_bytes: int | None = None,
                 prefix_cache: bool | None = None,
                 prefix_lru: int | None = None,
                 sched: str | None = None,
                 sched_aging: int | None = None,
                 preemption: bool | None = None,
                 overlap: bool | None = None,
                 draft_model: "ModelConfig | str | None" = None,
                 draft_params: PyTree | None = None,
                 spec_k: int | None = None,
                 split_pools: bool | None = None,
                 prefill_slots: int | None = None,
                 metrics: "MetricsRegistry | None" = None,
                 tracer=None):
        self.cfg, self.params = cfg, params
        self.max_slots, self.max_len = max_slots, max_len
        self.eos_id = eos_id
        self.part = part
        # observability: one shared metrics registry (allocator, prefix
        # index, and scheduler register into it) + a lifecycle tracer.
        # The default NULL_TRACER is a no-op hook — call sites emit
        # unconditionally, disabled tracing costs one empty method call.
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.trace = tracer if tracer is not None else NULL_TRACER
        self.paged = cfg.paged_kv if paged is None else paged
        self.page_size = page_size or cfg.page_size
        self.prefill_chunk = prefill_chunk or cfg.prefill_chunk
        # prefix caching shares fully-written prompt pages of the block pool
        # across requests (refcounted, copy-on-write). It requires every
        # cacheable layer state to live in the paged pools, so it is gated
        # on all-full-attention decoder configs: sliding-window rings and
        # recurrent carries are per-slot dense state a prefix hit cannot
        # restore. Incapable configs silently serve cold (prefix_hits == 0)
        # rather than erroring — the flag is a throughput hint, not a
        # semantics change.
        want_prefix = (cfg.prefix_cache if prefix_cache is None
                       else prefix_cache)
        self.prefix_capable = (self.paged and cfg.encoder is None
                               and all(sp.mixer == "full"
                                       for sp in cfg.all_layers()))
        self.prefix_cache = bool(want_prefix) and self.prefix_capable
        self.prefix_lru = (cfg.prefix_lru if prefix_lru is None
                           else prefix_lru)
        if self.prefix_lru < 0:     # engine kwarg / --prefix-lru bypasses
            raise ValueError("prefix_lru must be >= 0")
        # SPMD serving: a serve-mode Partitioner shards the paged KV pools
        # (and their per-row quant scales) over the model axis by KV head;
        # everything per-slot — block tables, lengths, sampling state —
        # stays replicated host metadata. ``kv_shard`` > 1 is the capacity
        # dividend: each device holds 1/kv_shard of every block.
        self._kv_shard = 1
        if self.paged and part is not None:
            if getattr(part, "mode", None) != "serve":
                raise ValueError(
                    "paged SPMD serving needs a serve-mode Partitioner "
                    "(Partitioner(..., mode='serve')): training-mode rules "
                    "shard batch/seq dims the block pool does not have")
            self._kv_shard = int(getattr(part, "kv_shard", 1))
        # scheduling policy layer: admission order, SLOs, fairness, aging
        self.scheduler = Scheduler(
            sched or cfg.sched_policy,
            aging_skips=cfg.sched_aging if sched_aging is None
            else sched_aging, metrics=self.metrics)
        self.preemption = cfg.preemption if preemption is None else preemption
        if self.preemption and not self.paged:
            raise ValueError("preemption requires the paged (block-pool) "
                             "layout: dense slots hold no reclaimable blocks")
        self.overlap = cfg.overlap_decode if overlap is None else overlap
        # multi-precision serving (repro.quant): post-load weight
        # quantization keyed off cfg.weight_dtype, applied here so callers
        # need no separate transform step. Under a serve-mode Partitioner
        # the quantized params are simply replicated (serve rules shard only
        # the KV pools), so the combination is fine.
        if cfg.weight_dtype:
            self.params = quantize_params(params, cfg)
        if is_quant_dtype(cfg.kv_dtype):
            if not self.paged:
                raise ValueError(
                    "kv_dtype requires the paged (block-pool) cache layout: "
                    "per-row scales live alongside the pools")
            if cfg.encoder is not None:
                raise ValueError(
                    "quantized KV does not support enc-dec models: the "
                    "whole-prompt prefill commit path writes dense rows")
        # kernel selection for the engine's jitted graphs: explicit arg >
        # cfg.kernel_backend; block tuning comes from the strategy when
        # serving under a Partitioner. Fixed for the engine's lifetime (the
        # scope must be active whenever a prefill/decode graph traces).
        self.kernel_backend = (kernel_backend or cfg.resolved_kernel_backend
                               or None)
        strat = getattr(part, "strategy", None)
        self._kernel_blocks = (kdispatch.blocks_from_pairs(strat.kernel_blocks)
                               if strat is not None and strat.kernel_blocks
                               else None)
        #: engine-level base key: per-request streams are derived from it
        #: by folding the request uid (or replaced by ``Request.seed``)
        self._base_key = np.asarray(jax.random.PRNGKey(seed), np.uint32)
        # speculative decoding: a small draft model proposes spec_k tokens
        # per turn; the verifier scores all of them plus one bonus position
        # in a single batched verify_step pass (see repro.spec)
        dm = draft_model if draft_model is not None else (
            cfg.draft_model or None)
        if isinstance(dm, str):
            from repro.configs import get_arch
            dm = get_arch(dm)
        self.spec_k = int(cfg.spec_k if spec_k is None else spec_k)
        self._draft_cfg = dm
        self.draft = None
        if dm is not None:
            if not self.prefix_capable:
                raise ValueError(
                    "speculative decoding requires the paged local "
                    "all-full-attention path: verify_step rolls uncommitted "
                    "rows back through the block allocator")
            if self.overlap:
                raise ValueError(
                    "speculative decoding and overlap_decode are exclusive: "
                    "the spec turn already overlaps draft and verifier work")
            if dm.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft model must share the verifier's vocabulary: "
                    f"{dm.vocab_size} != {cfg.vocab_size}")
            if self.spec_k < 1:
                self.spec_k = 4
        if self.paged:
            if kv_budget_bytes is not None:
                # size the pool by HBM budget through the cache's sizing
                # helper: the narrower the KV dtype, the more blocks the
                # same budget admits (dense-equivalent count is the cap)
                n_blocks = min(
                    n_blocks_for_bytes(cfg, kv_budget_bytes, self.page_size,
                                       kv_shard=self._kv_shard),
                    default_n_blocks(max_slots, max_len, self.page_size))
            else:
                n_blocks = (max_blocks or cfg.max_blocks
                            or default_n_blocks(max_slots, max_len,
                                                self.page_size))
            # pool leaves must be distinguishable from batch-sized leaves,
            # and a pool smaller than the slot count cannot serve anyway
            self.n_blocks = max(n_blocks, max_slots + 1)
            self.allocator = BlockAllocator(self.n_blocks, self.page_size,
                                            n_shards=self._kv_shard,
                                            metrics=self.metrics)
            if self.prefix_cache:
                self.prefix_index = PrefixIndex(self.page_size,
                                                max_cached=self.prefix_lru,
                                                metrics=self.metrics)
                self.allocator.evictor = self.prefix_index
            else:
                self.prefix_index = None
            self.n_pages = pages_per_slot(max_len, self.page_size)
            self.block_tables = np.zeros((max_slots, self.n_pages), np.int32)
            self.cache = init_cache(cfg, max_slots, max_len,
                                    n_blocks=self.n_blocks,
                                    page_size=self.page_size)
            self._cache_shardings = None
            if part is not None:
                # place pool leaves sharded by KV head over the model axis,
                # everything else replicated, and pin the layout so donation
                # round-trips through the jitted updates keep it stable
                self._cache_shardings = part.serve_cache_sharding(
                    self.cache, self.n_blocks)
                self.cache = jax.device_put(self.cache, self._cache_shardings)
                self.params = jax.device_put(
                    self.params, part.params_sharding(self.params))
            pool = kv_bytes(self.cache, pool_n_blocks=self.n_blocks)
            self._block_kv_bytes = pool // self.n_blocks
            # ring buffers / recurrent-adjacent dense KV still charge per slot
            self._slot_kv_bytes = (kv_bytes(self.cache) - pool) // max_slots
        else:
            self.allocator = None
            self.prefix_index = None
            self.n_blocks = 0
            self.block_tables = None
            self.cache = init_cache(cfg, max_slots, max_len)
            self._cache_shardings = None
            self._block_kv_bytes = 0
            self._slot_kv_bytes = kv_bytes(self.cache) // max_slots
        # disaggregated prefill/decode pools: the first ``prefill_slots``
        # slots chunk-prefill only; completed prompts hand their KV off to a
        # decode-pool slot purely by republishing pages through the block
        # table (a host-side int32 row copy — zero tensor traffic).
        self.split_pools = (cfg.split_pools if split_pools is None
                            else split_pools)
        n_pre = (cfg.prefill_slots if prefill_slots is None
                 else prefill_slots)
        if self.split_pools:
            if not self.paged:
                raise ValueError("split_pools requires the paged layout: "
                                 "the handoff republishes pool pages")
            if n_pre <= 0:
                n_pre = max(1, max_slots // 4)
            if not 0 < n_pre < max_slots:
                raise ValueError(
                    f"prefill_slots must leave both pools non-empty: "
                    f"{n_pre} of {max_slots} slots")
        self.prefill_slots = n_pre if self.split_pools else 0
        #: slot -> pool id (1 = prefill pool, 0 = decode pool / unified)
        self._slot_pool = np.zeros(max_slots, np.int8)
        if self.split_pools:
            self._slot_pool[:self.prefill_slots] = 1
        #: prefill-pool slots whose prompt is fully written, awaiting a
        #: decode-pool slot for the block-table handoff
        self._handoff_ready: set[int] = set()
        # slot bookkeeping (host side)
        self.phase = np.full(max_slots, FREE, np.int8)
        self.slot_uid = np.full(max_slots, -1, np.int64)
        #: next KV write position per slot — advanced at *dispatch* time, so
        #: with overlap it can run one step ahead of the synced token lists
        self.slot_pos = np.zeros(max_slots, np.int32)
        self.slot_budget = np.zeros(max_slots, np.int32)
        self.slot_temp = np.zeros(max_slots, np.float32)
        self.slot_blocks: list[list[int]] = [[] for _ in range(max_slots)]
        self._prefilling: dict[int, Request] = {}        # slot -> request
        self._admit_hashes: dict[int, list[int]] = {}    # uid -> page hashes
        self._prefill_off = np.zeros(max_slots, np.int32)
        #: absolute position of the first non-prefix-cached token per slot —
        #: chunked prefill starts here; everything below it was mapped
        #: read-only from shared blocks
        self._first_new = np.zeros(max_slots, np.int32)
        self._t0 = np.zeros(max_slots, np.float64)
        # per-slot scheduling state (preemption victims, requeue identity)
        self._slot_req: list[Request | None] = [None] * max_slots
        self._slot_legacy = np.zeros(max_slots, bool)
        self._slot_prio = np.zeros(max_slots, np.int32)
        self._slot_seq = np.zeros(max_slots, np.int64)   # admission recency
        self._slot_sched_seq = np.zeros(max_slots, np.int64)
        #: len(res.tokens) at admission — length finishes compare *emitted*
        #: tokens against the segment budget, because with overlap
        #: ``slot_budget`` is decremented at dispatch and runs one
        #: speculative step ahead of the synced token list
        self._slot_tok0 = np.zeros(max_slots, np.int64)
        # per-request sampling state: top-k/top-p knobs, PRNG base key and
        # dispatch counter (the in-jit key is fold(base, ctr, purpose))
        self.slot_topk = np.zeros(max_slots, np.int32)
        self.slot_topp = np.ones(max_slots, np.float32)
        self._slot_key = np.zeros((max_slots, 2), np.uint32)
        self._slot_ctr = np.zeros(max_slots, np.int64)
        #: token fed on a slot's first decode when nothing was emitted yet
        #: (fork children re-decode the prompt's last row to diverge)
        self._slot_feed = np.zeros(max_slots, np.int32)
        #: fork-family membership: parents with reserved children and the
        #: children themselves are never preemption victims (their shared
        #: refcounts would outlive the eviction)
        self._slot_fork = np.zeros(max_slots, bool)
        self._slot_children: dict[int, list[int]] = {}
        #: pages granted at admission — speculative extras roll back to this
        self._slot_base_pages = np.zeros(max_slots, np.int64)
        self._slot_first = np.zeros(max_slots, np.int32)
        self._next_child_uid = -2
        self._admit_seq = 0
        self._pending: _Pending | None = None
        self.results: dict[int, Result] = {}
        self._prefill_cache: dict[tuple, Any] = {}
        self._decode_fn = jax.jit(self._decode_all, donate_argnums=(1,))
        self._commit_fn = jax.jit(self._commit_slot, donate_argnums=(0,))
        self._chunk_fn = None
        self._copy_fn = jax.jit(
            lambda cache, src, dst: self._pin(
                copy_block(cache, src, dst, self.n_blocks)),
            donate_argnums=(0,))
        # the historical ``stats`` dict, rebuilt on the metrics registry:
        # every legacy key is a registered Counter/Gauge and ``self.stats``
        # is a dict-compatible live view over the registry (so
        # ``stats[k] += 1``, ``dict(engine.stats)``, and per-key reads all
        # behave exactly as before). Counters the scheduler / prefix index
        # own (``sched_skips``, ``prefix_evictions``) are the *same*
        # instrument objects — no per-step mirroring.
        for name, help_ in (
                ("prefills", "prompts placed into a slot"),
                ("decode_steps", "decode dispatches (batched steps)"),
                ("prefill_chunks", "chunked-prefill extend_step dispatches"),
                ("prefill_recompiles", "distinct compiled prefill shapes"),
                ("rejected", "requests rejected at submit/admission"),
                ("kv_bytes_alloc", "KV bytes allocated (global, lifetime)"),
                ("prefix_hits", "admissions that matched cached prefix pages"),
                ("prefix_hit_tokens", "prompt tokens skipped via prefix hits"),
                ("prefix_cow", "copy-on-write block privatizations"),
                ("prefix_evictions",
                 "cached blocks reclaimed to the free list"),
                ("preemptions", "slots evicted for higher-priority arrivals"),
                ("sched_skips",
                 "admission passes that overtook a blocked entry"),
                ("slo_met", "finished requests inside their SLO targets"),
                ("slo_missed", "finished requests outside their SLO targets"),
                ("spec_turns", "speculative draft+verify turns"),
                ("spec_proposed", "draft tokens proposed"),
                ("spec_accepted", "draft tokens accepted (incl. bonus)"),
                ("spec_extra_blocks", "blocks granted for spec overflow"),
                ("forks", "parallel-sampling fork fan-outs"),
                ("fork_shared_blocks", "prompt blocks shared COW at fork"),
                ("fork_fresh_blocks", "fresh blocks granted to fork children"),
                ("handoffs", "prefill->decode pool block-table handoffs"),
                ("handoff_wait_steps",
                 "steps a finished prefill waited for a decode slot"),
                ("decode_gap_steps",
                 "steps with queued work but no decode dispatched"),
                ("decode_window_tokens",
                 "tokens committed inside measured decode windows"),
                ("decode_window_batch",
                 "sum over decode dispatches of active slots"),
                ("decode_window_kv_rows",
                 "sum over decode dispatches of context rows attended"),
        ):
            self.metrics.counter(name, help_)
        for name, help_ in (
                ("kv_bytes_cached", "refcount-0 bytes retained by the index"),
                ("kv_bytes_alloc_dev", "per-device share of kv_bytes_alloc"),
                ("max_concurrency", "peak concurrently-active slots"),
        ):
            self.metrics.gauge(name, help_)
        self._h_decode_window = self.metrics.histogram(
            "decode_window_s",
            "measured wall seconds per engine step that dispatched decode "
            "work (joined against roofline/memfloor by repro.obs.report)")
        self._h_spec_accept = self.metrics.histogram(
            "spec_accept_len", "accepted tokens per speculative turn",
            buckets=tuple(float(b) for b in range(0, 17)))
        self._c_win_tokens = self.metrics.counter("decode_window_tokens")
        self._c_win_batch = self.metrics.counter("decode_window_batch")
        self._c_win_kv = self.metrics.counter("decode_window_kv_rows")
        self._c_finished = self.metrics.counter(
            "finished", "requests finished, by reason", labels=("reason",))
        self.stats = self.metrics.view()
        if self._draft_cfg is not None:
            self.draft = DraftWorker(
                self._draft_cfg, draft_params, max_slots=max_slots,
                max_len=max_len, k=self.spec_k,
                prefill_chunk=self.prefill_chunk, seed=seed + 1)
            self._spec_fn = jax.jit(self._spec_verify, donate_argnums=(1,))

    # ------------------------------------------------------------------
    @property
    def active(self) -> np.ndarray:
        """Slots currently owned by a request (prefilling or decoding)."""
        return self.phase != FREE

    @property
    def queue(self) -> list[Request]:
        """Waiting requests in arrival order (scheduler-owned)."""
        return [e.req for e in self.scheduler.entries()]

    def _kernel_scope(self):
        """Backend/block-tuning scope for prefill and decode graphs. SPMD
        serving never opens a kernel scope: forward/decode_step would
        neutralize it anyway (no pallas_call inside pjit)."""
        if self.part is not None:
            return contextlib.nullcontext()
        if self.kernel_backend or self._kernel_blocks:
            return kdispatch.use_backend(self.kernel_backend,
                                         blocks=self._kernel_blocks)
        return contextlib.nullcontext()

    def _tables(self):
        return _h2d(self.block_tables) if self.paged else None

    def _pin(self, cache):
        """Pin a jitted graph's output cache to the serve shardings so the
        donation round-trip keeps a stable (sharded-pool) layout across
        engine steps instead of letting propagation reshard per graph."""
        if self._cache_shardings is None:
            return cache
        return self.part.serve_cache_constraint(cache, self._cache_shardings)

    # ---- jitted graphs ------------------------------------------------
    def _decode_all(self, params, cache, tokens, pos, active, tables, temps,
                    topk, topp, keys, ctrs):
        """One decode step over the whole slot pool + fused sampling."""
        logits, cache = decode_step(params, self.cfg, cache, tokens, pos,
                                    part=self.part, active=active,
                                    block_tables=tables)
        kk = _fold_keys(keys, ctrs, _P_SAMPLE)
        return _sample(logits[:, 0], temps, topk, topp, kk), self._pin(cache)

    def _chunk_step(self, params, cache, tokens, pos, n_valid, slot, tables,
                    temp, topk, topp, key, ctr, first_new):
        """One chunked-prefill step for one slot + fused sampling (the
        sampled id only matters on the final chunk). ``first_new`` (traced
        scalar) is the absolute position prefill started at — positions
        below it come from prefix-shared blocks."""
        logits, cache = extend_step(params, self.cfg, cache, tokens, pos,
                                    n_valid, slot, block_tables=tables,
                                    first_new_pos=first_new, part=self.part)
        kk = _fold_keys(key[None], ctr[None], _P_SAMPLE)
        return _sample(logits[:, 0], temp[None], topk[None], topp[None],
                       kk), self._pin(cache)

    def _spec_verify(self, params, cache, feed, draft_toks, draft_probs,
                     pos, n_valid, active, tables, temps, topk, topp, keys,
                     ctrs):
        """One speculative verify turn, fully in-jit: score the last
        committed token plus the k draft proposals in a single batched
        ``verify_step`` pass, then run the distribution-preserving
        acceptance rule. Returns (out_tokens (B, k+1), n_accept (B), cache);
        only the committed prefix of ``out_tokens`` reaches the results."""
        toks = jnp.concatenate([feed, draft_toks], axis=1)
        logits, cache = verify_step(params, self.cfg, cache, toks, pos,
                                    n_valid, active=active,
                                    block_tables=tables, part=self.part)
        kk = _fold_keys(keys, ctrs, _P_ACCEPT)
        out, n_acc = speculative_accept(logits, draft_toks, draft_probs,
                                        temps, topk, topp, kk,
                                        n_draft=n_valid - 1)
        return out, n_acc, self._pin(cache)

    def _commit_slot(self, cache, slot_cache, slot, tables):
        """Write a batch-1 dense prefill cache into slot ``slot`` of the
        pooled cache (donated: cost scales with the written region). Paged
        pool leaves take the slot's rows through its block table; everything
        else (dense KV, ring buffers, recurrent states, cross caches) is a
        dynamic-slice update at the slot index."""
        page = self.page_size

        def f(path, b, s):
            keys = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
            axis = 1 if "blocks" in keys else 0
            if self.paged and b.shape[axis] == self.n_blocks:
                s_buf = s.shape[axis + 1]
                rows = jnp.arange(s_buf)
                trow = jax.lax.dynamic_slice(
                    tables, (slot, 0), (1, tables.shape[1]))[0]
                blk = trow[rows // page]
                r = rows % page
                if axis == 0:
                    return b.at[blk, r].set(s[0].astype(b.dtype), mode="drop")
                return b.at[:, blk, r].set(s[:, 0].astype(b.dtype),
                                           mode="drop")
            start = tuple(slot if i == axis else 0 for i in range(b.ndim))
            return jax.lax.dynamic_update_slice(b, s.astype(b.dtype), start)

        return jax.tree_util.tree_map_with_path(f, cache, slot_cache)

    def _ensure_chunk_fn(self):
        if self._chunk_fn is None:
            # one compiled shape serves every chunk of every prompt length
            self.stats["prefill_recompiles"] += 1
            self._chunk_fn = jax.jit(self._chunk_step, donate_argnums=(1,))
        return self._chunk_fn

    def _prefill_fn(self, length: int, has_frames: bool, has_extra: bool):
        """Legacy whole-prompt prefill (enc-dec / vlm / SPMD): jit per
        distinct prompt length — exactness over the recompile."""
        key = (length, has_frames, has_extra)
        if key not in self._prefill_cache:
            self.stats["prefill_recompiles"] += 1

            def fn(params, tokens, frames, extra):
                cache_t = init_cache(self.cfg, 1, self.max_len)
                hidden, cache, _ = forward(params, self.cfg, tokens,
                                           frames=frames, extra_embeds=extra,
                                           cache=cache_t, part=self.part)
                logits = logits_fn(params, self.cfg, hidden[:, -1:, :],
                                   self.part)[..., :self.cfg.vocab_size]
                return logits[:, 0], cache

            self._prefill_cache[key] = jax.jit(fn)
        return self._prefill_cache[key]

    # ---- streaming ----------------------------------------------------
    def submit(self, req: Request):
        self.results[req.uid] = Result(uid=req.uid,
                                       submit_s=time.perf_counter())
        self.scheduler.submit(req)
        self.trace.begin("request", req.uid,
                         prompt_tokens=len(req.prompt),
                         max_new=req.max_new_tokens)
        self.trace.begin("queue", req.uid)
        self.trace.event("submit", req.uid)

    def stream(self, req: Request, *, max_steps: int = 100000
               ) -> Iterator[int]:
        """Submit ``req`` and yield its tokens as they arrive, stepping the
        engine (and any other in-flight requests) between yields."""
        self.submit(req)
        res = self.results[req.uid]
        sent = steps = 0
        while True:
            while sent < len(res.tokens):
                yield res.tokens[sent]
                sent += 1
            if res.finish_reason:
                return
            if steps >= max_steps:
                self._truncate()
                continue
            self.step()
            steps += 1

    def _emit(self, slot: int, tok: int):
        """Append one sampled token to the slot's result: timestamped for
        TTFT/ITL accounting, streamed through the request's callback."""
        res = self.results[self.slot_uid[slot]]
        res.tokens.append(tok)
        res.token_ts.append(time.perf_counter())
        req = self._slot_req[slot]
        if req is not None and req.on_token is not None:
            req.on_token(tok, res)

    # ---- scheduling ----------------------------------------------------
    def _reject(self, req: Request, why: str):
        """Graceful per-request rejection: the engine loop keeps serving."""
        res = self.results[req.uid]
        res.finish_reason = "rejected"
        res.detail = why
        self._admit_hashes.pop(req.uid, None)
        self.stats["rejected"] += 1
        self._c_finished.inc(reason="rejected")
        self.trace.event("reject", req.uid, why=why[:120])
        self.trace.close_open(req.uid, reason="rejected")

    def _cow_pages(self, slot: int, lo: int, hi: int) -> None:
        """Copy-on-write guard before writing positions ``[lo, hi)`` of
        ``slot``: any touched page whose block is shared (refcount > 1) or
        pinned by the prefix index gets a private copy first (fresh block,
        jitted page copy, table remap). Admission already privatizes the one
        boundary page a prefix hit can write, so this keeps 'writers never
        touch shared blocks' true by construction rather than by scheduling
        luck. Fork children lean on the same guard: their shared prompt
        pages carry refcount > 1 whether or not a prefix index exists."""
        if not self.paged or hi <= lo:
            return
        page = self.page_size
        for p in range(lo // page, (hi - 1) // page + 1):
            blk = int(self.block_tables[slot, p])
            if blk == 0:
                continue
            if (self.allocator.ref[blk] > 1
                    or (self.prefix_index is not None
                        and self.prefix_index.is_cached(blk))):
                [dst] = self.allocator.alloc(1)
                self.cache = self._copy_fn(self.cache, np.int32(blk),
                                           np.int32(dst))
                self.allocator.release([blk])
                self.slot_blocks[slot][
                    self.slot_blocks[slot].index(blk)] = dst
                self.block_tables[slot, p] = dst
                self.stats["prefix_cow"] += 1
                self.trace.event("cow", int(self.slot_uid[slot]), slot=slot,
                                 page=p)

    # ---- preemption ----------------------------------------------------
    def _preempt_for(self, prio: int, pool: int | None = None) -> bool:
        """Free resources for a priority-``prio`` arrival: evict one victim
        slot of strictly lower priority (lowest class first, then the most
        recently admitted — the least sunk work). Returns True when anything
        may have freed, so the caller re-checks fit before preempting more.
        ``pool`` restricts victims to one side of a split-pool engine (a
        blocked handoff may only evict decode-pool slots).

        A pending overlapped decode is flushed first: its in-flight sampled
        ids must land before a victim's generated tokens are folded into its
        resumption prompt (and the flush itself can finish slots, making
        the preemption unnecessary)."""
        if not self.preemption:
            return False
        if self._pending is not None:
            self._sync_pending()
            return True
        cands = [s for s in range(self.max_slots)
                 if self.phase[s] != FREE and not self._slot_legacy[s]
                 and not self._slot_fork[s]
                 and self._slot_prio[s] < prio
                 and (pool is None or self._slot_pool[s] == pool)]
        if not cands:
            return False
        victim = max(cands, key=lambda s: (-int(self._slot_prio[s]),
                                           int(self._slot_seq[s])))
        self._preempt(victim)
        return True

    def _preempt(self, slot: int) -> None:
        """Evict ``slot``: publish its fully-written pages into the prefix
        index (so resumption is a warm hit, not a recompute), release its
        blocks through the refcounted path (indexed pages stay cached,
        fresh ones free — a mid-prefill victim rolls back exactly like a
        failed admission), and requeue the request with its generated
        tokens folded into the prompt at its original place in line."""
        uid = int(self.slot_uid[slot])
        res = self.results[uid]
        req = self._slot_req[slot]
        if self.phase[slot] == PREFILL:
            written = int(self._prefill_off[slot])
            new_prompt = np.asarray(req.prompt, np.int32)
            self._prefilling.pop(slot, None)
        else:
            # rows [0, slot_pos) are written; the last sampled token's KV is
            # not (it would be written by the next decode step), so the
            # resumption prompt = written tokens + that trailing token, and
            # its chunked prefill re-derives exactly the logits decode
            # would have produced next
            written = int(self.slot_pos[slot])
            gen = [t for t in res.tokens[len(res.tokens)
                                         - (written + 1
                                            - len(req.prompt)):]]
            new_prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(gen, np.int32)])
        new_budget = int(self.slot_budget[slot])
        if self.prefix_index is not None:
            n_full = written // self.page_size
            if n_full:
                # full pages of the written region (prompt AND generated
                # tokens) are valid chain entries: the resumption — or any
                # request sharing the extended prefix — adopts them
                seq_tokens = (new_prompt if self.phase[slot] != PREFILL
                              else np.asarray(req.prompt, np.int32))
                self.prefix_index.publish(seq_tokens,
                                          self.slot_blocks[slot][:n_full])
        self.allocator.release(self.slot_blocks[slot])
        if self.prefix_index is not None:
            self.prefix_index.trim(self.allocator)
        self.slot_blocks[slot] = []
        self.block_tables[slot, :] = 0
        self.phase[slot] = FREE
        self.slot_uid[slot] = -1
        self._slot_req[slot] = None
        self._handoff_ready.discard(slot)
        if self.draft is not None:
            self.draft.drop(slot)
        res.preempted += 1
        self.stats["preemptions"] += 1
        self.scheduler.requeue(
            dc_replace(req, prompt=new_prompt, max_new_tokens=new_budget),
            seq=int(self._slot_sched_seq[slot]), submit_s=res.submit_s)
        self.trace.event("preempt", uid, slot=slot, written=written)
        # phase spans close; the request span stays open across the requeue
        self.trace.close_open(uid, keep=("request",), slot=slot,
                              reason="preempted")
        self.trace.begin("queue", uid)
        self.trace.event("requeue", uid)

    # ---- admission -----------------------------------------------------
    def _free_slot(self, pool: int | None = None) -> int | None:
        for s in range(self.max_slots):
            if self.phase[s] != FREE:
                continue
            if pool is not None and self._slot_pool[s] != pool:
                continue
            return s
        return None

    def _note_skip(self, entry) -> None:
        """Record an admission pass-over: scheduler aging + trace events."""
        was = self.scheduler.reserved(entry)
        self.scheduler.note_skip(entry)
        self.trace.event("queue_skip", entry.req.uid, skips=entry.skips)
        if not was and self.scheduler.reserved(entry):
            self.trace.event("aged", entry.req.uid)

    def _admit(self):
        """Fill free slots in scheduler order. A request blocked on pool
        resources is *skipped* (smaller ones behind it admit now — the
        head-of-line fix) and aged: once promoted to a reservation, nothing
        overtakes it until it admits. Impossible requests reject instead of
        crashing the loop; with preemption enabled, a blocked high-priority
        request evicts lower-priority victims first."""
        guard = 0
        while self.scheduler and guard <= 4 * self.max_slots + 8:
            guard += 1
            if not self._admit_pass():
                return

    def _admit_pass(self) -> bool:
        """One pass over the scheduler order. Returns True when a
        preemption changed the resource picture and the pass should
        restart."""
        fcfs = self.scheduler.policy == "fcfs"
        for entry in self.scheduler.order():
            req = entry.req
            n_tokens = len(req.prompt) + req.max_new_tokens
            if n_tokens > self.max_len:
                self.scheduler.remove(entry)
                self._reject(req, f"exceeds max_len: prompt+budget "
                                  f"{n_tokens} tokens > {self.max_len}")
                continue
            if (self.part is not None and self.paged
                    and (self.cfg.encoder is not None
                         or req.frames is not None
                         or req.extra_embeds is not None)):
                # enc-dec / vlm inputs need the dense whole-prompt prefill
                # path, which commits batch-1 rows the sharded pools cannot
                # take — reject gracefully instead of crashing the loop
                ndev = int(getattr(self.part.mesh, "size", 1))
                self.scheduler.remove(entry)
                self._reject(
                    req,
                    f"unsupported on sharded KV pools: enc-dec/vlm "
                    f"requests use the dense whole-prompt prefill path, "
                    f"which does not run over the {ndev}-device serve mesh")
                continue
            legacy = (self.cfg.encoder is not None
                      or req.frames is not None
                      or req.extra_embeds is not None
                      or (self.part is not None and not self.paged))
            if legacy and is_quant_dtype(self.cfg.kv_dtype):
                # the whole-prompt prefill commit writes dense rows —
                # incompatible with quantized pools
                self.scheduler.remove(entry)
                self._reject(req, "quantized KV serves chunked-prefill "
                                  "requests only (no frames/embeds)")
                continue
            n_par = max(1, int(req.n))
            if n_par > 1 and (legacy or not self.prefix_capable):
                self.scheduler.remove(entry)
                self._reject(req, "parallel sampling (n > 1) requires the "
                                  "paged local all-full-attention path")
                continue
            if n_par > self.max_slots:
                self.scheduler.remove(entry)
                self._reject(req, f"n {n_par} exceeds max_slots "
                                  f"{self.max_slots}")
                continue
            if self.paged:
                total = self.allocator.pages_for(n_tokens)
                if total > self.allocator.capacity:
                    cap = self.allocator.capacity
                    self.scheduler.remove(entry)
                    self._reject(
                        req,
                        f"exceeds block pool: needs {total} blocks "
                        f"({total * self._block_kv_bytes} KV bytes) > "
                        f"capacity {cap} blocks "
                        f"({cap * self._block_kv_bytes} KV bytes)")
                    continue
            # split pools: chunked prefills start in the prefill pool
            # (pool 1) and hand off; legacy whole-prompt requests go
            # straight to a decode-pool slot (their prefill is synchronous)
            want_pool = (None if not self.split_pools
                         else (0 if legacy else 1))
            slot = self._free_slot(want_pool)
            if slot is None:
                if self._preempt_for(int(req.priority), pool=want_pool):
                    return True              # resources moved: re-plan
                return False                 # every slot busy: nobody admits
            if n_par > 1:
                if self.split_pools:
                    # children and the parent's eventual handoff all land
                    # in the decode pool
                    short = sum(1 for s in range(self.max_slots)
                                if self.phase[s] == FREE
                                and self._slot_pool[s] == 0) < n_par
                else:
                    short = int((self.phase == FREE).sum()) < n_par
                if short:
                    # the whole fan-out needs slots up front (children are
                    # reserved at admission); no preemption to make room —
                    # fan-outs wait rather than evict
                    self._note_skip(entry)
                    if fcfs or self.scheduler.reserved(entry):
                        return False
                    continue
            if self.paged:
                if not self._admit_paged(entry, slot, n_tokens, legacy):
                    if fcfs or self.scheduler.reserved(entry):
                        # FCFS never overtakes; a reserved (aged) entry
                        # holds the pool until it fits
                        return False
                    continue
            else:
                self._first_new[slot] = 0
                self.stats["kv_bytes_alloc"] += self._slot_kv_bytes
            self._place(entry, slot, legacy)
        return False

    def _admit_paged(self, entry, slot: int, n_tokens: int,
                     legacy: bool) -> bool:
        """Block-pool admission for one request: prefix lookup, grant, COW.
        Returns False (after noting the skip) when blocks are short even
        after preemption."""
        req = entry.req
        total = self.allocator.pages_for(n_tokens)
        # prefix cache: map the longest indexed chain of this prompt's
        # pages read-only into the slot's block table (refcount++ per
        # page) and prefill only the tail
        matched: list[int] = []
        first_new = 0
        if self.prefix_cache and not legacy:
            # hash once per request: a request stalled on free blocks
            # retries every step and must not re-hash its whole prompt
            hs = self._admit_hashes.get(req.uid)
            if hs is None:
                hs = page_hashes(req.prompt, self.page_size)
                self._admit_hashes[req.uid] = hs
            matched = self.prefix_index.lookup(
                req.prompt, self.allocator, hashes=hs)
            # clamp below by 0: an empty prompt must not push the
            # prefill offset negative
            first_new = max(0, min(len(matched) * self.page_size,
                                   len(req.prompt) - 1))
        # a page-aligned full-prompt match still recomputes the final
        # token (its logits seed decode), so the last matched page gets
        # written mid-page -> privatize it now via copy-on-write
        # (counted into the grant, so the pool can never strand a
        # request mid-COW)
        cow = (bool(matched)
               and first_new < len(matched) * self.page_size)
        need = total - len(matched) + (1 if cow else 0)
        while (need > self.allocator.n_available
               and self._preempt_for(int(req.priority))):
            pass                      # each eviction is re-checked
        if need > self.allocator.n_available:
            # hand the prefix references back (refcount-0 indexed blocks
            # return to cached, not freed) and note the skip for aging
            self.allocator.release(matched)
            self._note_skip(entry)
            return False
        try:
            fresh = self.allocator.alloc(need)
        except RuntimeError:
            # alloc rolled its partial grant back; hand the prefix
            # references back too — admission leaves no trace
            self.allocator.release(matched)
            self._note_skip(entry)
            return False
        if cow:
            shared = matched[-1]
            matched[-1] = fresh.pop(0)
            self.cache = self._copy_fn(
                self.cache, np.int32(shared), np.int32(matched[-1]))
            self.allocator.release([shared])
            self.stats["prefix_cow"] += 1
            self.trace.event("cow", req.uid, slot=slot,
                             page=len(matched) - 1)
        blocks = matched + fresh
        self.slot_blocks[slot] = blocks
        self.block_tables[slot, :] = 0
        self.block_tables[slot, :len(blocks)] = blocks
        self._first_new[slot] = first_new
        self.stats["kv_bytes_alloc"] += (
            need * self._block_kv_bytes + self._slot_kv_bytes)
        if matched:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += first_new
        return True

    def _request_key(self, req: Request) -> np.ndarray:
        """Per-request PRNG base key: ``Request.seed`` when given (exact
        replay across runs), else derived from the engine seed and the uid
        — either way independent of admission order and co-residents."""
        if req.seed is not None:
            return np.asarray(jax.random.PRNGKey(int(req.seed)), np.uint32)
        return np.asarray(
            jax.random.fold_in(_h2d(self._base_key),
                               np.uint32(req.uid & 0xFFFFFFFF)), np.uint32)

    def _place(self, entry, slot: int, legacy: bool) -> None:
        """Bind an admitted request to its slot and start prefill."""
        req = entry.req
        # fan-outs charge their full decode cost: n sequences each draw up
        # to max_new_tokens against the user's service accumulator
        self.scheduler.note_admitted(
            entry, len(req.prompt) + max(1, int(req.n)) * req.max_new_tokens)
        self._admit_hashes.pop(req.uid, None)
        self._t0[slot] = time.perf_counter()
        self.slot_uid[slot] = req.uid
        self.slot_temp[slot] = req.temperature
        self.slot_budget[slot] = req.max_new_tokens
        self._slot_req[slot] = req
        self._slot_legacy[slot] = legacy
        self._slot_prio[slot] = req.priority
        self._slot_seq[slot] = self._admit_seq
        self._slot_sched_seq[slot] = entry.seq
        self._slot_tok0[slot] = len(self.results[req.uid].tokens)
        self._admit_seq += 1
        self.stats["prefills"] += 1
        self.trace.end("queue", req.uid, slot=slot)
        self.trace.begin("prefill", req.uid, slot=slot)
        res = self.results[req.uid]
        self.trace.event("admit", req.uid, slot=slot,
                         first_new=int(self._first_new[slot]),
                         pages=len(self.slot_blocks[slot]),
                         resumed=res.preempted > 0)
        self.slot_topk[slot] = max(0, int(req.top_k))
        self.slot_topp[slot] = float(req.top_p)
        self._slot_key[slot] = self._request_key(req)
        self._slot_ctr[slot] = len(self.results[req.uid].tokens)
        self._slot_feed[slot] = int(req.prompt[-1]) if len(req.prompt) else 0
        self._slot_base_pages[slot] = (len(self.slot_blocks[slot])
                                       if self.paged else 0)
        if self.draft is not None and not legacy:
            self.draft.begin(slot)
        if legacy:
            self._prefill_whole(slot, req)
        else:
            self.phase[slot] = PREFILL
            self._prefilling[slot] = req
            # chunked prefill starts at the first non-cached token:
            # everything below rode in read-only through the table
            self._prefill_off[slot] = self._first_new[slot]
            if int(req.n) > 1:
                # the parent's phase is set: reservation sees it as busy
                self._reserve_children(slot, entry)

    def _reserve_children(self, slot: int, entry) -> None:
        """Reserve one free slot per extra sample of a ``Request(n > 1)``.
        Reserved slots sit inert (phase PREFILL, zero budget, not in
        ``_prefilling``) until the parent's prefill completes and
        ``_fork_children`` maps the shared pages; the whole family is
        preemption-exempt so the shared refcounts cannot outlive a victim."""
        req = entry.req
        res = self.results[req.uid]
        kids: list[int] = []
        pool = 0 if self.split_pools else None
        for i in range(int(req.n) - 1):
            # guaranteed by the admission count (decode pool when split)
            cs = self._free_slot(pool)
            cuid = self._next_child_uid
            self._next_child_uid -= 1
            cres = Result(uid=cuid, submit_s=res.submit_s)
            res.children.append(cres)
            self.results[cuid] = cres
            self.trace.begin("request", cuid, slot=cs, parent=req.uid)
            self.phase[cs] = PREFILL
            self.slot_uid[cs] = cuid
            self.slot_temp[cs] = req.temperature
            self.slot_budget[cs] = 0
            self.slot_topk[cs] = max(0, int(req.top_k))
            self.slot_topp[cs] = float(req.top_p)
            self._slot_req[cs] = dc_replace(req, uid=cuid, n=1)
            self._slot_legacy[cs] = False
            self._slot_prio[cs] = req.priority
            self._slot_seq[cs] = self._admit_seq
            self._slot_sched_seq[cs] = entry.seq
            self._slot_tok0[cs] = 0
            self._slot_fork[cs] = True
            # child streams branch off the parent key through a fork tag:
            # child i is reproducible given (request seed, i)
            self._slot_key[cs] = np.asarray(jax.random.fold_in(
                jax.random.fold_in(_h2d(self._slot_key[slot]),
                                   np.uint32(_P_FORK)),
                np.uint32(i + 1)), np.uint32)
            self._slot_ctr[cs] = 0
            kids.append(cs)
        self._slot_fork[slot] = True
        self._slot_children[slot] = kids

    def _fork_children(self, parent: int, req: Request) -> None:
        """COW-fork a prefilled parent into its reserved children. Shared
        prompt pages map read-only into each child's table (refcount++);
        the boundary page holding the prompt's last row is privatized per
        child, because the child re-decodes that row to sample its own
        first token; fresh pages back each child's future tail. A child
        whose fresh grant cannot be allocated rejects gracefully — the
        parent and remaining children keep going."""
        P = len(req.prompt)
        kids = self._slot_children.pop(parent, [])
        pblocks = self.slot_blocks[parent]
        w0 = (P - 1) // self.page_size      # page the child rewrites
        total = self.allocator.pages_for(P + req.max_new_tokens)
        for cs in kids:
            child_req = self._slot_req[cs]
            try:
                fresh = self.allocator.alloc(total - w0)
            except RuntimeError:
                self._reject(child_req, "fork: block pool exhausted")
                self.phase[cs] = FREE
                self.slot_uid[cs] = -1
                self._slot_req[cs] = None
                self._slot_fork[cs] = False
                continue
            for blk in pblocks[:w0]:
                self.allocator.incref(blk)
            # private copy of the boundary page: it holds committed rows
            # below P-1 that the family shares but this child must own
            self.cache = self._copy_fn(self.cache, np.int32(pblocks[w0]),
                                       np.int32(fresh[0]))
            blocks = list(pblocks[:w0]) + fresh
            self.slot_blocks[cs] = blocks
            self.block_tables[cs, :] = 0
            self.block_tables[cs, :len(blocks)] = blocks
            self.phase[cs] = DECODE
            self.slot_pos[cs] = P - 1
            self.slot_budget[cs] = req.max_new_tokens
            self._slot_feed[cs] = int(req.prompt[-1])
            self._slot_base_pages[cs] = len(blocks)
            self._prefill_off[cs] = 0
            self._first_new[cs] = 0
            self._t0[cs] = self._t0[parent]
            if self.draft is not None and self.draft.off[parent] >= 0:
                self.draft.fork_slot(parent, cs)
            self.stats["forks"] += 1
            self.stats["fork_shared_blocks"] += w0
            self.stats["fork_fresh_blocks"] += len(fresh)
            self.stats["kv_bytes_alloc"] += len(fresh) * self._block_kv_bytes
            self.trace.event("fork", int(self.slot_uid[cs]), slot=cs,
                             parent=req.uid, shared=w0, fresh=len(fresh))
            self.trace.begin("decode", int(self.slot_uid[cs]), slot=cs)

    def _prefill_whole(self, slot: int, req: Request):
        prompt = np.asarray(req.prompt, np.int32)[None]  # (1, S)
        length = prompt.shape[1]
        fn = self._prefill_fn(length, req.frames is not None,
                              req.extra_embeds is not None)
        frames = (jnp.asarray(req.frames)[None]
                  if req.frames is not None else None)
        extra = (jnp.asarray(req.extra_embeds)[None]
                 if req.extra_embeds is not None else None)
        with self._kernel_scope():
            logits, slot_cache = fn(self.params, jnp.asarray(prompt),
                                    frames, extra)
        self.cache = self._commit_fn(self.cache, slot_cache, np.int32(slot),
                                     self._tables())
        kk = _fold_keys(
            _h2d(self._slot_key[slot][None]),
            jnp.asarray([self._slot_ctr[slot] & 0x7FFFFFFF], jnp.uint32),
            _P_SAMPLE)
        first = int(_sample(logits,
                            jnp.asarray([req.temperature], jnp.float32),
                            _h2d(self.slot_topk[slot][None]),
                            _h2d(self.slot_topp[slot][None]),
                            kk)[0])
        self._slot_ctr[slot] += 1
        self.phase[slot] = DECODE
        self._finish_prefill(slot, first, length)

    def _prefill_chunks(self):
        """Advance every mid-prefill slot by one ``prefill_chunk``-token
        chunk (ragged tails pad to the same compiled shape behind
        ``n_valid``); decode interleaves between chunks, so a long prompt
        never stalls the running slots."""
        for slot in sorted(self._prefilling):
            req = self._prefilling[slot]
            prompt = np.asarray(req.prompt, np.int32)
            if (self.draft is not None and self.draft.off[slot] >= 0
                    and not self.draft.ready(slot, len(prompt))):
                # the draft prefills its own dense cache in lockstep —
                # always from 0: prefix hits are a verifier-pool concept
                self.draft.prefill_chunk(slot, prompt)
            off = int(self._prefill_off[slot])
            if off < len(prompt):
                t = min(self.prefill_chunk, len(prompt) - off)
                buf = np.zeros((1, self.prefill_chunk), np.int32)
                buf[0, :t] = prompt[off:off + t]
                fn = self._ensure_chunk_fn()
                self._cow_pages(slot, off, off + t)
                with self._kernel_scope():
                    tok, self.cache = fn(
                        self.params, self.cache, jnp.asarray(buf),
                        np.int32(off), np.int32(t), np.int32(slot),
                        self._tables(), np.float32(req.temperature),
                        np.int32(self.slot_topk[slot]),
                        np.float32(self.slot_topp[slot]),
                        _h2d(self._slot_key[slot]),
                        np.uint32(self._slot_ctr[slot] & 0x7FFFFFFF),
                        np.int32(self._first_new[slot]))
                self._slot_ctr[slot] += 1
                self.stats["prefill_chunks"] += 1
                self.trace.event("prefill_chunk", int(self.slot_uid[slot]),
                                 slot=slot, off=off, n=t)
                off += t
                self._prefill_off[slot] = off
                if off >= len(prompt):
                    self._slot_first[slot] = int(tok[0])
            if off < len(prompt):
                continue
            if (self.draft is not None and self.draft.off[slot] >= 0
                    and not self.draft.ready(slot, len(prompt))):
                continue        # verifier done; draft still catching up
            del self._prefilling[slot]
            if self.prefix_index is not None:
                # every full prompt page is now written: publish the
                # slot's pages so later identical prefixes can share
                # them (matched pages re-register as a no-op; cold
                # concurrent duplicates stay un-indexed and free
                # normally at finish)
                n_full = len(prompt) // self.page_size
                if n_full:
                    self.prefix_index.publish(
                        prompt, self.slot_blocks[slot][:n_full])
            if self.split_pools and self._slot_pool[slot] == 1:
                # disaggregated handoff: children fork off the shared pages
                # now (they already hold decode-pool slots), then the
                # parent's prompt KV moves pools purely by republishing its
                # pages through the block table
                if self._slot_children.get(slot):
                    self._fork_children(slot, req)
                self._handoff_ready.add(slot)
                self._try_handoffs()
                continue
            self.phase[slot] = DECODE
            if self._slot_children.get(slot):
                # fork before the parent can finish: children must map the
                # prompt pages while they are all still resident
                self._fork_children(slot, req)
            self._finish_prefill(slot, int(self._slot_first[slot]),
                                 len(prompt))

    # ---- disaggregated prefill/decode pools ----------------------------
    def _move_slot(self, src: int, dst: int) -> None:
        """Relocate a request between slots. The KV handoff is the block-
        table row copy: pages stay exactly where they are in the (possibly
        mesh-sharded) pool, the destination slot simply republishes them —
        zero tensor traffic on any mesh. Refcounts are untouched: the
        blocks change owner, not reference count."""
        self.block_tables[dst, :] = self.block_tables[src, :]
        self.block_tables[src, :] = 0
        self.slot_blocks[dst] = self.slot_blocks[src]
        self.slot_blocks[src] = []
        for arr in (self.phase, self.slot_uid, self.slot_pos,
                    self.slot_budget, self.slot_temp, self.slot_topk,
                    self.slot_topp, self._slot_ctr, self._slot_feed,
                    self._prefill_off, self._first_new, self._t0,
                    self._slot_legacy, self._slot_prio, self._slot_seq,
                    self._slot_sched_seq, self._slot_tok0, self._slot_fork,
                    self._slot_base_pages, self._slot_first):
            arr[dst] = arr[src]
        self._slot_key[dst] = self._slot_key[src]
        self._slot_req[dst] = self._slot_req[src]
        self._slot_req[src] = None
        if src in self._slot_children:
            self._slot_children[dst] = self._slot_children.pop(src)
        if self.draft is not None and self.draft.off[src] >= 0:
            # the draft's dense cache row moves with the request
            self.draft.fork_slot(src, dst)
            self.draft.drop(src)
        self.phase[src] = FREE
        self.slot_uid[src] = -1
        self._slot_fork[src] = False

    def _try_handoffs(self) -> None:
        """Move each prefill-pool slot whose prompt KV is fully written
        into a decode-pool slot (evicting a strictly-lower-priority decode
        slot when preemption allows). A blocked handoff counts wait steps
        instead of stalling the engine — the prefill slot stays parked
        until a decode slot frees."""
        for src in sorted(self._handoff_ready):
            dst = self._free_slot(pool=0)
            if dst is None and self._preempt_for(
                    int(self._slot_prio[src]), pool=0):
                dst = self._free_slot(pool=0)
            if dst is None:
                self.stats["handoff_wait_steps"] += 1
                self.trace.event("handoff_wait", int(self.slot_uid[src]),
                                 slot=src)
                continue
            self._handoff_ready.discard(src)
            req = self._slot_req[src]
            self._move_slot(src, dst)
            self.phase[dst] = DECODE
            self.stats["handoffs"] += 1
            self.trace.event("handoff", int(self.slot_uid[dst]), slot=dst,
                             src=src)
            self._finish_prefill(dst, int(self._slot_first[dst]),
                                 len(req.prompt))

    def _emitted(self, slot: int) -> int:
        """Tokens emitted in this admission segment (synced to host)."""
        return (len(self.results[self.slot_uid[slot]].tokens)
                - int(self._slot_tok0[slot]))

    def _finish_prefill(self, slot: int, first: int, length: int):
        uid = int(self.slot_uid[slot])
        res = self.results[uid]
        self.trace.end("prefill", uid, slot=slot, length=length)
        self.trace.begin("decode", uid, slot=slot)
        self._emit(slot, first)
        if res.prefill_s == 0.0:    # resumption keeps the original TTFT
            res.prefill_s = time.perf_counter() - self._t0[slot]
        self.slot_pos[slot] = length  # position of `first` when decoded
        self.slot_budget[slot] -= 1
        if self.eos_id is not None and first == self.eos_id:
            self._finish(slot, "eos")
        elif self._emitted(slot) >= self._slot_req[slot].max_new_tokens:
            self._finish(slot, "length")

    def _finish(self, slot: int, reason: str):
        uid = int(self.slot_uid[slot])
        res = self.results[uid]
        res.finish_reason = reason
        self._c_finished.inc(reason=reason)
        self.trace.event("finish", uid, slot=slot, reason=reason,
                         tokens=len(res.tokens))
        self.trace.close_open(uid, slot=slot, reason=reason)
        req = self._slot_req[slot]
        if (req is not None and reason in ("eos", "length")
                and (req.slo_ttft_ms is not None
                     or req.slo_itl_ms is not None)):
            ok = True
            if req.slo_ttft_ms is not None:
                ok &= (res.ttft_s is not None
                       and res.ttft_s * 1e3 <= req.slo_ttft_ms)
            if req.slo_itl_ms is not None and res.itl_s is not None:
                ok &= res.itl_s * 1e3 <= req.slo_itl_ms
            res.slo_met = bool(ok)
            self.stats["slo_met" if ok else "slo_missed"] += 1
        self.phase[slot] = FREE
        self.slot_uid[slot] = -1
        self._slot_req[slot] = None
        self._prefilling.pop(slot, None)
        self._handoff_ready.discard(slot)
        if self.draft is not None:
            self.draft.drop(slot)
        self._slot_fork[slot] = False
        # reserved-but-never-forked children (parent truncated mid-prefill)
        # hold no blocks and finish independently through the drain loop
        self._slot_children.pop(slot, None)
        if self.paged and self.slot_blocks[slot]:
            # drop this slot's references immediately: unshared blocks are
            # admittable this very step, and fully-written prompt pages
            # that made it into the prefix index stay resident as cached
            # (refcount-0, LRU-evictable) blocks instead of freeing
            self.allocator.release(self.slot_blocks[slot])
            if self.prefix_index is not None:
                self.prefix_index.trim(self.allocator)
            self.slot_blocks[slot] = []
            self.block_tables[slot, :] = 0

    # ---- speculative decoding ------------------------------------------
    def _committed_tok(self, slot: int, p: int) -> int:
        """Token at absolute position ``p`` of the slot's committed stream:
        the prompt, then this segment's emitted tokens (a resumption folds
        earlier generations into the prompt, so the formula holds across
        preemptions; fork children start with an empty segment)."""
        req = self._slot_req[slot]
        if p < len(req.prompt):
            return int(req.prompt[p])
        res = self.results[self.slot_uid[slot]]
        return int(res.tokens[int(self._slot_tok0[slot])
                              + (p - len(req.prompt))])

    def _rollback_spec(self, slot: int) -> None:
        """Roll the slot's speculative pages back through the allocator:
        release every page beyond what the committed stream needs (never
        below the admission grant — those pages are the request's own)."""
        keep = max(int(self._slot_base_pages[slot]),
                   self.allocator.pages_for(int(self.slot_pos[slot]) + 1))
        while len(self.slot_blocks[slot]) > keep:
            blk = self.slot_blocks[slot].pop()
            self.block_tables[slot, len(self.slot_blocks[slot])] = 0
            self.allocator.release([blk])

    def _spec_turn(self) -> np.ndarray | None:
        """One speculative draft-verify turn over every eligible DECODE
        slot. The draft proposes up to ``spec_k`` tokens per slot from its
        dense cache; the verifier scores the last committed token plus all
        proposals in one batched ``verify_step``; the acceptance rule
        commits a distribution-preserving prefix (plus one bonus/residual
        token); uncommitted verifier rows roll back through the block
        allocator. Returns the mask of slots handled here so the plain
        decode path skips them, or None when no slot was eligible."""
        k = self.spec_k
        mask = np.zeros(self.max_slots, bool)
        k_eff = np.zeros(self.max_slots, np.int32)
        feed0 = np.zeros((self.max_slots, 1), np.int32)
        feed1 = np.zeros((self.max_slots, 1), np.int32)
        for slot in range(self.max_slots):
            if (self.phase[slot] != DECODE or self.slot_budget[slot] <= 0
                    or self._slot_legacy[slot]
                    or self.draft.off[slot] < 0):
                continue
            req = self._slot_req[slot]
            pos0 = int(self.slot_pos[slot])
            if pos0 < 1 or not self.draft.ready(slot, len(req.prompt)):
                continue    # fall back to plain decode this turn
            ke = min(k, self.max_len - 1 - pos0)
            if ke < 1:
                continue
            # speculative pages: rows [pos0, pos0+ke] must be backed; the
            # extras beyond the admission grant are transient (rolled back
            # after the commit). On pool pressure, clamp ke to what the
            # current grant backs instead of stalling the slot.
            need = self.allocator.pages_for(pos0 + ke + 1)
            extra = need - len(self.slot_blocks[slot])
            if extra > 0:
                try:
                    got = self.allocator.alloc(extra)
                except RuntimeError:
                    got = []
                if got:
                    base = len(self.slot_blocks[slot])
                    self.slot_blocks[slot].extend(got)
                    self.block_tables[slot, base:base + len(got)] = got
                    self.stats["spec_extra_blocks"] += len(got)
                else:
                    ke = (len(self.slot_blocks[slot]) * self.page_size
                          - 1 - pos0)
                    if ke < 1:
                        continue
            mask[slot] = True
            k_eff[slot] = ke
            feed0[slot, 0] = self._committed_tok(slot, pos0 - 1)
            feed1[slot, 0] = self._committed_tok(slot, pos0)
            # verify writes rows [pos0, pos0+ke]: privatize shared pages
            self._cow_pages(slot, pos0, pos0 + ke + 1)
        if not mask.any():
            return None
        active = jnp.asarray(mask)
        temps = _h2d(self.slot_temp)
        topk = _h2d(self.slot_topk)
        topp = _h2d(self.slot_topp)
        keys = _h2d(self._slot_key)
        ctrs = jnp.asarray((self._slot_ctr & 0x7FFFFFFF).astype(np.uint32))
        pos = _h2d(self.slot_pos)
        n_valid = jnp.asarray(np.where(mask, k_eff + 1, 1).astype(np.int32))
        with self._kernel_scope():
            dtoks, dprobs = self.draft.propose(
                jnp.asarray(feed0), jnp.asarray(feed1), pos, active, temps,
                topk, topp, keys, ctrs)
            if self.part is not None:
                # the draft runs single-device; re-materialize its outputs
                # host-side so the mesh-sharded verify graph can place them
                dtoks, dprobs = np.asarray(dtoks), np.asarray(dprobs)
            out, n_acc, self.cache = self._spec_fn(
                self.params, self.cache, jnp.asarray(feed1), dtoks, dprobs,
                pos, n_valid, active, self._tables(), temps, topk, topp,
                keys, ctrs)
        out = np.asarray(out)
        n_acc = np.asarray(n_acc)
        self.stats["spec_turns"] += 1
        nd = int(mask.sum())
        self._c_win_batch.inc(nd)
        self._c_win_kv.inc(int(self.slot_pos[mask].sum()) + nd)
        self.trace.event("spec_propose", n=nd,
                         kv=int(self.slot_pos[mask].sum()) + nd)
        for slot in np.nonzero(mask)[0]:
            self._slot_ctr[slot] += 1
            req = self._slot_req[slot]
            res = self.results[self.slot_uid[slot]]
            ke = int(k_eff[slot])
            na = min(int(n_acc[slot]), ke)
            self.stats["spec_proposed"] += ke
            self.stats["spec_accepted"] += na
            if req.user is not None:
                # draft-token budget accounting: proposing ke tokens costs
                # the user ke tokens of service whether or not they commit
                self.scheduler.charge(req.user, ke)
            finish = None
            committed = 0
            for j in range(min(na + 1, int(self.slot_budget[slot]))):
                tok = int(out[slot, j])
                self._emit(slot, tok)
                committed += 1
                if self.eos_id is not None and tok == self.eos_id:
                    finish = "eos"
                    break
                if self._emitted(slot) >= req.max_new_tokens:
                    finish = "length"
                    break
            res.decode_steps += 1
            self.stats["decode_steps"] += 1
            self.slot_pos[slot] += committed
            self.slot_budget[slot] -= committed
            self._c_win_tokens.inc(committed)
            self._h_spec_accept.observe(na)
            self.trace.event("spec_commit", int(self.slot_uid[slot]),
                             slot=int(slot), proposed=ke, accepted=na,
                             tokens=committed)
            n_extra = len(self.slot_blocks[slot])
            self._rollback_spec(slot)
            n_rolled = n_extra - len(self.slot_blocks[slot])
            if n_rolled:
                self.trace.event("spec_rollback", int(self.slot_uid[slot]),
                                 slot=int(slot), pages=n_rolled)
            if finish is not None:
                self._finish(slot, finish)
        return mask

    # ---- decode (double-buffered) --------------------------------------
    def _decode(self):
        """Dispatch one decode step, then sync. Without overlap the sync is
        immediate (legacy behavior). With overlap the *previous* step's ids
        sync after this step's dispatch is already on the device — host
        bookkeeping and the next admission run while the device computes,
        at the cost of ids reaching callbacks one step late."""
        t0 = time.perf_counter()
        skip = self._spec_turn() if self.draft is not None else None
        prev = self._pending
        self._pending = self._dispatch_decode(prev, skip=skip)
        did = (self._pending is not None
               or (skip is not None and bool(skip.any())))
        if not did and (bool(self.scheduler) or bool(self._handoff_ready)):
            # requests are queued or parked awaiting handoff but no decode
            # was issued: the decode side sat idle this step. In a unified
            # engine this gap grows with prompt length (prefill occupies
            # the slots); split pools keep it flat — the gate the
            # throughput benchmark checks.
            self.stats["decode_gap_steps"] += 1
        if prev is not None:
            self._sync(prev)
        if not self.overlap and self._pending is not None:
            p, self._pending = self._pending, None
            self._sync(p)
        if did:
            # measured decode window: sync-visible wall seconds for one
            # step that dispatched decode work (repro.obs.report joins
            # these against the roofline/memfloor model)
            self._h_decode_window.observe(time.perf_counter() - t0)

    def _dispatch_decode(self, prev: _Pending | None,
                         skip: np.ndarray | None = None
                         ) -> _Pending | None:
        """Enqueue one decode step on device. Continuing slots take their
        token feed from ``prev``'s device ids (never synced to host);
        slots that just finished prefill take their host-known first token.
        Positions and budgets advance at dispatch, so the mask and the COW
        guard stay exact even while ids are in flight. ``skip`` masks out
        slots a speculative turn already advanced this step."""
        dec = (self.phase == DECODE) & (self.slot_budget > 0)
        if skip is not None:
            dec &= ~skip
        if not dec.any():
            return None
        tokens = np.zeros((self.max_slots, 1), np.int32)
        for slot in np.nonzero(dec)[0]:
            if self._emitted(slot) > 0:
                res = self.results[self.slot_uid[slot]]
                tokens[slot, 0] = res.tokens[-1]
            else:
                # nothing emitted yet this segment: a fork child re-decodes
                # the prompt's last token to sample its own first one
                tokens[slot, 0] = self._slot_feed[slot]
            # a decode write to a shared page privatizes it first
            self._cow_pages(slot, int(self.slot_pos[slot]),
                            int(self.slot_pos[slot]) + 1)
        feed = jnp.asarray(tokens)
        if prev is not None:
            # double-buffer: the last sampled ids are still on device
            feed = jnp.where(jnp.asarray(prev.mask)[:, None],
                             prev.ids[:, None], feed)
        with self._kernel_scope():
            ids, self.cache = self._decode_fn(
                self.params, self.cache, feed,
                _h2d(self.slot_pos), jnp.asarray(dec), self._tables(),
                _h2d(self.slot_temp),
                _h2d(self.slot_topk), _h2d(self.slot_topp),
                _h2d(self._slot_key),
                jnp.asarray((self._slot_ctr & 0x7FFFFFFF).astype(np.uint32)))
        self._slot_ctr[dec] += 1
        self.stats["decode_steps"] += 1
        # window accounting at dispatch, before pos advances: rows attended
        # this step = prior context + the token being written per slot
        nd = int(dec.sum())
        rows = int(self.slot_pos[dec].sum()) + nd
        self._c_win_batch.inc(nd)
        self._c_win_kv.inc(rows)
        self.trace.event("dispatch", n=nd, kv=rows)
        self.slot_pos[dec] += 1
        self.slot_budget[dec] -= 1
        return _Pending(ids=ids, mask=dec, uids=self.slot_uid.copy())

    def _sync(self, p: _Pending):
        """Bring one dispatched decode step's sampled ids to host and run
        the bookkeeping: stream/append tokens, finish on eos or exhausted
        budget. Ids for requests that finished while the step was in
        flight (an eos discovered one sync earlier) are discarded — their
        slot was dispatched speculatively."""
        ids = np.asarray(p.ids)
        n_emitted = 0
        for slot in np.nonzero(p.mask)[0]:
            uid = int(p.uids[slot])
            res = self.results.get(uid)
            if (res is None or res.finish_reason
                    or self.slot_uid[slot] != uid):
                continue                    # speculative overflow step
            tok = int(ids[slot])
            self._emit(slot, tok)
            n_emitted += 1
            res.decode_steps += 1
            if self.eos_id is not None and tok == self.eos_id:
                self._finish(slot, "eos")
            elif self._emitted(slot) >= self._slot_req[slot].max_new_tokens:
                # emitted-count check, NOT slot_budget: with overlap the
                # budget already paid for the next in-flight dispatch
                self._finish(slot, "length")
        # tokens become measured throughput only once sync-visible
        self._c_win_tokens.inc(n_emitted)
        self.trace.event("sync", n=int(p.mask.sum()), tokens=n_emitted)

    def _sync_pending(self):
        """Flush the overlapped decode step, if any (idempotent)."""
        p, self._pending = self._pending, None
        if p is not None:
            self._sync(p)

    # ---- engine loop ---------------------------------------------------
    def step(self) -> int:
        """Admit, retry parked handoffs, advance prefill chunks, one decode
        step. Returns #busy."""
        self._admit()
        if self._handoff_ready:
            self._try_handoffs()
        self._prefill_chunks()
        self._decode()
        # (sched_skips / prefix_evictions need no mirroring: the scheduler
        # and prefix index increment the same registry counters directly)
        if self.prefix_index is not None:
            # cached-block accounting: KV bytes held by refcount-0 pages
            # retained for future prefix hits (reclaimable, so they are
            # reported separately from kv_bytes_alloc)
            self.stats["kv_bytes_cached"] = (
                self.prefix_index.n_evictable(self.allocator)
                * self._block_kv_bytes)
        n_busy = int((self.phase != FREE).sum())
        self.stats["max_concurrency"] = max(self.stats["max_concurrency"],
                                            n_busy)
        # per-device KV footprint: pool bytes divide across kv_shard
        # devices (dense per-slot leaves are replicated, but all-full
        # paged configs have none)
        self.stats["kv_bytes_alloc_dev"] = (
            self.stats["kv_bytes_alloc"] // max(self._kv_shard, 1))
        return n_busy

    def _busy(self) -> bool:
        return (bool(self.scheduler) or bool((self.phase != FREE).any())
                or self._pending is not None)

    def _truncate(self):
        """Drain a run that hit ``max_steps``: flush the overlapped step so
        no sampled token is lost, finish every in-flight slot as
        ``truncated`` (blocks released — leak-free), and mark still-queued
        requests the same way. Partial tokens stay on the Result."""
        self._sync_pending()
        for slot in range(self.max_slots):
            if self.phase[slot] == FREE:
                continue
            res = self.results[self.slot_uid[slot]]
            res.detail = ("prefill interrupted at max_steps"
                          if self.phase[slot] == PREFILL
                          else "decode interrupted at max_steps")
            self._finish(slot, "truncated")
        for entry in self.scheduler.drain():
            res = self.results.get(entry.req.uid)
            self._admit_hashes.pop(entry.req.uid, None)
            if res is not None and not res.finish_reason:
                res.finish_reason = "truncated"
                res.detail = "still queued at max_steps"
                self._c_finished.inc(reason="truncated")
                self.trace.event("truncate", entry.req.uid)
                self.trace.close_open(entry.req.uid, reason="truncated")

    def run(self, requests: list[Request], *, max_steps: int = 100000
            ) -> list[Result]:
        """Drive all requests to completion (continuous batching loop).
        Hitting ``max_steps`` truncates cleanly: in-flight slots release
        their blocks and every unfinished request gets
        ``finish_reason="truncated"`` instead of a half-populated Result."""
        for r in requests:
            self.submit(r)
        steps = 0
        while self._busy() and steps < max_steps:
            self.step()
            steps += 1
        if self._busy():
            self._truncate()
        return [self.results[r.uid] for r in requests]
