"""Config dataclasses for the repro framework.

Every assigned architecture is expressed as a ``ModelConfig``; shapes
(train/prefill/decode/long-context) are ``ShapeConfig``; the distribution
strategy (occamy/ramora/ogopogo — the paper's three generations) is a
``StrategyConfig``.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field


# --------------------------------------------------------------------------
# Layer specs
# --------------------------------------------------------------------------
# mixer: "full" | "local" | "rglru" | "mamba" | "mla" | "cross" (enc-dec
#        decoder adds cross attention automatically when cfg.encoder is set;
#        "mla" is multi-head latent attention, sized by cfg.mla)
# mlp:   "dense" | "moe" | "none"
@dataclass(frozen=True)
class LayerSpec:
    mixer: str = "full"
    mlp: str = "dense"


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    n_shared: int = 0
    d_expert: int = 1408          # per-expert FFN hidden size
    d_shared: int = 0             # total shared-expert hidden (0 => n_shared*d_expert)
    capacity_factor: float = 1.25
    renorm_topk: bool = True      # renormalize top-k gate weights (deepseek: yes, qwen2moe: no)
    shared_gate: bool = False     # qwen2-moe gates the shared expert output
    router_dtype: str = "float32"

    @property
    def shared_hidden(self) -> int:
        return self.d_shared if self.d_shared else self.n_shared * self.d_expert


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2): keys and values of every
    head are up-projected from one ``kv_lora_rank``-wide latent per token,
    beside a ``qk_rope_head_dim``-wide key that all heads share and that
    alone carries the rotary positions. The decode cache holds the latent
    and the shared key only."""
    kv_lora_rank: int = 512
    q_lora_rank: int = 0          # query compression: not built, must be 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Columns of one cached token per layer: the latent, then the
        shared RoPE key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (arXiv:2309.00071, as DeepSeek-V2 applies it):
    inverse frequencies blended between the base ones and those over
    ``factor`` by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations at
    ``original_max_position``; cos/sin scaled by ``mscale(mscale) /
    mscale(mscale_all_dim)``, and the softmax scale of latent attention by
    ``mscale(mscale_all_dim)**2`` where ``mscale_all_dim`` is set."""
    kind: str = "yarn"
    factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def __post_init__(self):
        if self.kind != "yarn":
            raise ValueError(f"rope_scaling kind {self.kind!r}; only 'yarn'")


@dataclass(frozen=True)
class SSMConfig:
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0              # 0 => ceil(d_model / 16)
    chunk: int = 256              # selective-scan chunk length


@dataclass(frozen=True)
class RGLRUConfig:
    lru_width: int = 0            # 0 => d_model
    d_conv: int = 4
    block_width: int = 0          # 0 => d_ff of the gated branch (uses cfg.d_ff)
    c_exponent: float = 8.0
    chunk: int = 256


@dataclass(frozen=True)
class EncoderConfig:
    n_layers: int = 4
    n_frames: int = 1500          # encoder sequence length (precomputed frontend frames)
    is_causal: bool = False


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "dense"         # dense | hybrid | moe | ssm | audio | vlm
    n_layers: int = 2
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 32000
    head_dim: int = 0             # 0 => d_model // n_heads
    # layer layout: prefix (unrolled) + pattern (scanned) + remainder (unrolled)
    prefix: tuple[LayerSpec, ...] = ()
    pattern: tuple[LayerSpec, ...] = (LayerSpec("full", "dense"),)
    # attention details
    window: int = 0               # sliding window for "local" mixers
    attn_softcap: float = 0.0
    final_softcap: float = 0.0
    attn_scale: float = 0.0       # 0 => 1/sqrt(head_dim); gemma2-27b: 144
    sandwich_norms: bool = False  # gemma2: pre+post norms around attn/mlp
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    use_rope: bool = True         # rotary positions
    learned_pos: bool = False     # whisper: learned absolute positions
    max_position: int = 1 << 16   # learned-position table size
    # blocks
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    rglru: RGLRUConfig | None = None
    encoder: EncoderConfig | None = None
    frontend: str | None = None   # None | "audio" | "vision"
    n_frontend_tokens: int = 0    # precomputed embedding tokens prepended (vlm)
    # misc
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    act: str = "silu"             # silu | gelu
    gated_mlp: bool = True
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    embed_scale: bool = False     # gemma-style sqrt(d_model) embedding scaling
    # compute / memory policy
    dtype: str = "bfloat16"       # activation/compute dtype
    param_dtype: str = "float32"  # master params
    # multi-precision quantization (repro.quant) — the paper's 8-to-64-bit
    # axis. ``weight_dtype`` ("" | int8 | fp8 | float8_e4m3fn) selects
    # weight-only post-training quantization: quantize_params() wraps matmul
    # weights in QuantTensor containers (per-channel absmax scales;
    # ``quant_block`` > 0 adds per-block scales along the contraction axis)
    # and the gemm_wq registry op dequantizes in-tile. ``kv_dtype`` stores
    # the paged KV block pools at the narrow width with per-row scales
    # (paged layout only — dense buffers keep ``dtype``). Serving-side
    # knobs: training always uses the dense master params.
    # ``weight_dtype`` additionally accepts "int4" (nibble-packed, weight-
    # only: KV pools stay byte-addressable). ``weight_density`` is the
    # structured-sparsity fraction of weight blocks kept nonzero (1.0 =
    # dense) — a cost-model knob consumed by the memfloor/roofline byte and
    # FLOP terms for gemm_sparse serving paths.
    weight_dtype: str = ""        # "" | int8 | fp8 | float8_e4m3fn | int4
    kv_dtype: str = ""            # "" | int8 | fp8 | float8_e4m3fn
    quant_block: int = 0          # 0 => per-channel; else scale-block length
    weight_density: float = 1.0   # (0, 1] nonzero weight-block fraction
    remat: str = "block"          # none | block (remat each scanned block)
    scan_unroll: int = 1          # block-scan unroll factor. Analysis builds
                                  # lower u=1 and u=2 and extrapolate, since
                                  # XLA cost_analysis counts while-bodies once.
    attn_chunk: int = 1024        # q-chunk for the jnp flash attention
    loss_chunk: int = 0           # 0 => full logits; >0 => chunked vocab loss
    # paged-KV serving (repro.serve.engine). ``paged_kv`` selects the
    # block-pool cache layout for full-attention layers (local windows,
    # recurrent states, and cross caches stay dense); ``page_size`` is the
    # KV rows per block; ``prefill_chunk`` is the fixed token count of the
    # one compiled chunked-prefill step; ``max_blocks`` sizes the global
    # block pool (0 => the engine derives max_slots * ceil(max_len /
    # page_size) + 1, i.e. dense-equivalent capacity plus the null block).
    paged_kv: bool = False
    page_size: int = 16
    prefill_chunk: int = 64
    max_blocks: int = 0
    # prefix caching (repro.serve.prefix): share fully-written prompt pages
    # of the block pool across requests (refcounted, copy-on-write) and
    # skip prefill for matched pages. ``prefix_lru`` caps how many
    # refcount-0 cached blocks the index retains after their owners finish
    # (0 = bounded only by pool pressure). Paged all-full-attention decoder
    # configs only; others serve cold.
    prefix_cache: bool = False
    prefix_lru: int = 0
    # serving scheduler (repro.serve.scheduler): admission policy over the
    # waiting queue. ``sched_policy`` is "priority" (priority classes, EDF
    # on TTFT SLOs, multi-tenant fair queuing, skip-with-aging — FCFS-
    # equivalent when requests carry no priorities/users/SLOs) or "fcfs"
    # (strict arrival order, legacy no-overtaking behavior). ``sched_aging``
    # is the skipped-admission-pass count that promotes a blocked request to
    # a pool reservation (0 = never, unbounded overtaking). ``preemption``
    # lets a blocked higher-priority request evict a lower-priority slot
    # (paged layout only); ``overlap_decode`` double-buffers the decode
    # dispatch so host bookkeeping overlaps device compute (token streams
    # identical, ids surface one step later).
    sched_policy: str = "priority"
    sched_aging: int = 64
    preemption: bool = False
    overlap_decode: bool = False
    # disaggregated prefill/decode pools (repro.serve.engine): partition
    # the slot pool so ``prefill_slots`` slots only chunk-prefill and the
    # rest only decode; a finished prompt hands its KV to a decode slot by
    # republishing pages through the block table (zero tensor copies).
    # ``prefill_slots`` 0 => auto (max(1, max_slots // 4)).
    split_pools: bool = False
    prefill_slots: int = 0
    # speculative decoding (repro.spec): ``draft_model`` names a registry
    # arch whose (smaller) model proposes ``spec_k`` tokens per scheduler
    # turn from its own dense cache; the serving model verifies all of
    # them in one batched pass and commits a distribution-preserving
    # prefix (exact greedy parity at temperature 0). Paged local
    # all-full-attention configs only; "" disables. ``spec_k=0`` takes
    # the engine default (4).
    draft_model: str = ""
    spec_k: int = 0
    # kernel selection flows through the backend registry
    # (repro.kernels.dispatch): "" keeps the pure-XLA paths (the only option
    # for training — kernel backends are forward/inference paths); "auto"
    # opts into the Pallas kernels when the platform has them (TPU); "ref" |
    # "interpret" | "pallas" pin a registry backend for the whole model
    # graph. A use_backend(...) scope around the model call overrides this
    # field. Read through ``resolved_kernel_backend``.
    kernel_backend: str = ""      # "" | auto | ref | interpret | pallas

    def __post_init__(self):
        if self.mla is None and any(sp.mixer == "mla" for sp in
                                    self.prefix + self.pattern):
            raise ValueError("an 'mla' mixer needs cfg.mla (MLAConfig)")
        if self.mla is not None and self.mla.q_lora_rank:
            raise ValueError("MLAConfig.q_lora_rank: query compression is "
                             "not built; queries project from x (0)")
        if self.kernel_backend not in ("", "auto", "ref", "interpret",
                                       "pallas"):
            raise ValueError(
                f"kernel_backend={self.kernel_backend!r}; expected '', "
                "'auto', 'ref', 'interpret', or 'pallas'")
        if self.page_size < 1 or self.prefill_chunk < 1:
            raise ValueError("page_size and prefill_chunk must be >= 1")
        if self.prefix_lru < 0:
            raise ValueError("prefix_lru must be >= 0")
        if self.sched_policy not in ("fcfs", "priority"):
            raise ValueError(
                f"sched_policy={self.sched_policy!r}; expected 'fcfs' or "
                "'priority'")
        if self.sched_aging < 0:
            raise ValueError("sched_aging must be >= 0")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        if self.draft_model and not self.paged_kv:
            raise ValueError("draft_model requires paged_kv=True: "
                             "speculative rollback reclaims verifier pages "
                             "through the block allocator")
        if self.preemption and not self.paged_kv:
            raise ValueError("preemption requires paged_kv=True: dense "
                             "slots hold no reclaimable blocks")
        if self.split_pools and not self.paged_kv:
            raise ValueError("split_pools requires paged_kv=True: the "
                             "prefill->decode handoff republishes pool "
                             "pages through the block table")
        if self.prefill_slots < 0:
            raise ValueError("prefill_slots must be >= 0 (0 = auto)")
        _quant_names = ("", "int8", "fp8", "float8_e4m3fn")
        # int4 is weight-only: KV pool rows must stay byte-addressable
        if self.weight_dtype not in _quant_names + ("int4",):
            raise ValueError(
                f"weight_dtype={self.weight_dtype!r}; expected one of "
                f"{_quant_names + ('int4',)}")
        if self.kv_dtype not in _quant_names:
            raise ValueError(
                f"kv_dtype={self.kv_dtype!r}; expected one of {_quant_names}")
        if self.quant_block < 0:
            raise ValueError("quant_block must be >= 0")
        if not 0.0 < self.weight_density <= 1.0:
            raise ValueError("weight_density must be in (0, 1]")

    @property
    def resolved_kernel_backend(self) -> str:
        """Backend for model layers: "" keeps the pure-XLA paths."""
        return self.kernel_backend

    # ---- derived ----
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    def layer_specs(self) -> tuple[tuple[LayerSpec, ...], tuple[LayerSpec, ...], int,
                                   tuple[LayerSpec, ...]]:
        """Return (prefix, pattern, n_repeats, remainder) covering n_layers."""
        n_rest = self.n_layers - len(self.prefix)
        assert n_rest >= 0, "prefix longer than n_layers"
        per = len(self.pattern)
        n_rep = n_rest // per
        rem = self.pattern[: n_rest - n_rep * per]
        return self.prefix, self.pattern, n_rep, rem

    def all_layers(self) -> list[LayerSpec]:
        prefix, pattern, n_rep, rem = self.layer_specs()
        return list(prefix) + list(pattern) * n_rep + list(rem)

    def param_count(self) -> dict[str, float]:
        """Analytic parameter counts (total, active, embedding)."""
        d, hd = self.d_model, self.resolved_head_dim
        embed = self.vocab_size * d
        if not self.tie_embeddings:
            embed *= 2
        total = 0.0
        active = 0.0
        for spec in self.all_layers():
            # mixer
            if spec.mixer in ("full", "local"):
                p = d * hd * (self.n_heads * 2 + self.n_kv_heads * 2)
                total += p
                active += p
            elif spec.mixer == "mla":
                a = self.mla
                H = self.n_heads
                p = (d * H * a.qk_head_dim + d * a.row_width
                     + a.kv_lora_rank * H * (a.qk_nope_head_dim
                                             + a.v_head_dim)
                     + H * a.v_head_dim * d)
                total += p
                active += p
            elif spec.mixer == "rglru":
                w = self.rglru.lru_width or d
                # two in-projections + out-projection + conv + Lambda
                p = 3 * d * w + w * self.rglru.d_conv + w
                p += 2 * w * (w // 8)  # block-diagonal (8 blocks) a/input gates
                total += p
                active += p
            elif spec.mixer == "mamba":
                di = self.ssm.expand * d
                dtr = self.ssm.dt_rank or math.ceil(d / 16)
                p = (d * 2 * di            # in_proj (x, z)
                     + di * self.ssm.d_conv
                     + di * (dtr + 2 * self.ssm.d_state)
                     + dtr * di
                     + di * self.ssm.d_state   # A_log
                     + di                       # D
                     + di * d)             # out_proj
                total += p
                active += p
            # mlp
            mult = 3 if self.gated_mlp else 2
            if spec.mlp == "dense":
                p = mult * d * self.d_ff
                total += p
                active += p
            elif spec.mlp == "moe":
                m = self.moe
                routed = m.n_experts * mult * d * m.d_expert
                shared = mult * d * m.shared_hidden if m.shared_hidden else 0
                router = d * m.n_experts
                total += routed + shared + router
                active += m.top_k * mult * d * m.d_expert + shared + router
            # cross attention (decoder of enc-dec)
            if self.encoder is not None and spec.mixer in ("full", "local"):
                p = d * hd * (self.n_heads * 2 + self.n_kv_heads * 2)
                total += p
                active += p
        if self.encoder is not None:
            for _ in range(self.encoder.n_layers):
                p = d * hd * (self.n_heads * 2 + self.n_kv_heads * 2)
                p += (3 if self.gated_mlp else 2) * d * self.d_ff
                total += p
                active += p
        total += embed
        active += embed
        return {"total": float(total), "active": float(active),
                "embedding": float(self.vocab_size * d),
                "nonembed_total": float(total - embed),
                "nonembed_active": float(active - embed)}

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------------
# Shapes
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class ShapeConfig:
    name: str
    kind: str                     # train | prefill | decode
    seq_len: int
    global_batch: int
    microbatches: int = 1         # grad-accumulation microbatches (train only)


# --------------------------------------------------------------------------
# Distribution strategies — the paper's three generations
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class StrategyConfig:
    name: str = "ramora"
    multi_pod: bool = False
    fsdp: bool = True               # shard params over 'data' (ZeRO-3)
    tensor_parallel: bool = True    # shard heads/d_ff/vocab over 'model'
    expert_parallel: bool = True    # shard experts over 'model' when divisible
    context_parallel_decode: bool = True  # shard KV length over 'data' for long decode
    seq_shard: bool = True          # sequence-parallel residual stream (Megatron-SP)
    hierarchical_collectives: bool = False  # ogopogo in-router analogue
    chunked_loss: bool = False      # ogopogo: chunked vocab xent
    grad_compression: str = "none"  # none | int8_ef
    overlap_microbatches: int = 1   # >1: grad-accum loop to overlap comm/compute
    remat: str = "block"
    # kernel block-size tuning overrides for the op registry: hashable tuple
    # of (op, bucket, ((kwarg, size), ...)) entries, bucket "*" = any shape
    # bucket. Decoded by repro.kernels.dispatch.blocks_from_pairs and applied
    # by call sites that own a strategy (e.g. the serve engine).
    kernel_blocks: tuple = ()

    @property
    def mesh_axes(self) -> tuple[str, ...]:
        return ("pod", "data", "model") if self.multi_pod else ("data", "model")


OCCAMY = StrategyConfig(name="occamy", fsdp=False, tensor_parallel=False,
                        expert_parallel=False, context_parallel_decode=False,
                        seq_shard=False)
RAMORA = StrategyConfig(name="ramora")
OGOPOGO = StrategyConfig(name="ogopogo", multi_pod=True,
                         hierarchical_collectives=True, chunked_loss=True,
                         overlap_microbatches=1)
# Beyond-paper (perf hillclimb, EXPERIMENTS.md §Perf): for dense training the
# per-layer TP activation psums (2 x (B,S,d) x {fwd,remat,bwd}) dwarf the
# weight traffic whenever B_loc*S*d >> layer params; spreading the model axis
# into the data/FSDP dimension trades them for one weight all-gather per pass.
# MoE archs keep expert parallelism over 'model' (the paper's packed-stream
# dispatch) — only the dense TP psums are removed.
FSDP2D = StrategyConfig(name="fsdp2d", tensor_parallel=False, seq_shard=False,
                        chunked_loss=True)
FSDP2D_POD = dataclasses.replace(FSDP2D, multi_pod=True,
                                 hierarchical_collectives=True)


def strategy(name: str, multi_pod: bool | None = None) -> StrategyConfig:
    base = {"occamy": OCCAMY, "ramora": RAMORA, "ogopogo": OGOPOGO,
            "fsdp2d": FSDP2D}[name]
    if multi_pod is not None:
        base = dataclasses.replace(base, multi_pod=multi_pod)
    return base
