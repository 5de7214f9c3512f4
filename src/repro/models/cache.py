"""Decode-cache construction (KV buffers, recurrent states).

Two layouts share one pytree *structure* (so jitted decode graphs are
layout-agnostic up to leaf shapes):

* **dense** — every slot statically reserves ``max_len`` KV rows per
  full-attention layer: leaves ``(batch, S_buf, K, hd)``.
* **paged** — full-attention layers share a global pool of fixed-size
  blocks, ``(n_blocks, page_size, K, hd)``, addressed through per-slot
  block tables (``(batch, P)`` int32, owned by the serve engine and passed
  alongside the cache). Block 0 is the *null block*: never allocated,
  it absorbs masked/inactive writes. Local (sliding-window) ring buffers,
  recurrent (RG-LRU / Mamba) states, and cross-attention caches stay dense
  in both layouts — they are already O(window) / O(1) per slot.

Multi-head latent attention layers (mixer ``"mla"``, DeepSeek-V2) cache
one *latent row* per token instead of per-head K/V: the RMS-normed
compressed KV followed by the RoPE'd shared key, ``kv_lora_rank +
qk_rope_head_dim`` columns, zero-padded to a whole number of 128-lane tiles
(:func:`latent_width`: 576 -> 640 at DeepSeek-V2-Lite's widths; the TPU's
HBM tiling pads a 576-wide minor dim to 640 all the same, and the decode
kernel copies whole rows by DMA). Paged: ``{"lat": (n_blocks, page, W)}``
under the same block tables, allocator and prefix cache; dense: ``{"lat":
(batch, max_len, W)}``. Latent pools are bf16/f32 only: a quantized
``kv_dtype`` is refused.

With ``cfg.kv_dtype`` set to a quantized dtype ("int8" / "fp8"), the paged
pools store K/V at the narrow width plus per-row float16 absmax scales
(``k_scale``/``v_scale`` leaves, shape ``(n_blocks, page_size, K)``) —
writes quantize per token row, reads dequantize through the
``paged_attention`` registry op. The sizing helpers (:func:`kv_bytes`,
:func:`kv_block_bytes`, :func:`n_blocks_for_bytes`) count the storage
dtype, so the same HBM budget admits proportionally more blocks
(docs/quantization.md). Quantized KV is a paged-layout feature; dense
buffers keep the compute dtype.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.quant import canonical_dtype, is_quant_dtype

#: dtype of the paged pools' per-row absmax scales.
KV_SCALE_DTYPE = jnp.float16


def latent_width(cfg: ModelConfig) -> int:
    """Stored columns of one latent row: the latent and the shared RoPE
    key, rounded up to whole 128-lane tiles (the pad stays zero)."""
    return -(-cfg.mla.row_width // 128) * 128


def _layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int, max_len: int,
                 dtype, n_blocks: int = 0, page_size: int = 0) -> dict:
    c: dict = {}
    hd, K = cfg.resolved_head_dim, cfg.n_kv_heads
    if spec.mixer == "mla":
        if is_quant_dtype(cfg.kv_dtype):
            raise ValueError(
                f"kv_dtype={cfg.kv_dtype!r}: quantized latent (MLA) pools "
                "are not supported; latent rows are stored in cfg.dtype")
        shp = ((n_blocks, page_size) if n_blocks else (batch, max_len))
        c["self"] = {"lat": jnp.zeros(shp + (latent_width(cfg),), dtype)}
    elif spec.mixer in ("full", "local"):
        if spec.mixer == "full" and n_blocks:
            # block-pool layout: global pool, no batch dim (slots address it
            # through block tables)
            kv_dt = dtype
            if is_quant_dtype(cfg.kv_dtype):
                kv_dt = jnp.dtype(canonical_dtype(cfg.kv_dtype))
            c["self"] = {"k": jnp.zeros((n_blocks, page_size, K, hd), kv_dt),
                         "v": jnp.zeros((n_blocks, page_size, K, hd), kv_dt)}
            if kv_dt != dtype:
                shp = (n_blocks, page_size, K)
                c["self"]["k_scale"] = jnp.zeros(shp, KV_SCALE_DTYPE)
                c["self"]["v_scale"] = jnp.zeros(shp, KV_SCALE_DTYPE)
        else:
            s_buf = max_len
            if spec.mixer == "local" and cfg.window:
                s_buf = min(cfg.window, max_len)
            c["self"] = {"k": jnp.zeros((batch, s_buf, K, hd), dtype),
                         "v": jnp.zeros((batch, s_buf, K, hd), dtype)}
        if cfg.encoder is not None:
            c["cross"] = {"k": jnp.zeros((batch, cfg.encoder.n_frames, K, hd), dtype),
                          "v": jnp.zeros((batch, cfg.encoder.n_frames, K, hd), dtype)}
    elif spec.mixer == "rglru":
        w = cfg.rglru.lru_width or cfg.d_model
        c["rec"] = {"h": jnp.zeros((batch, w), jnp.float32),
                    "conv": jnp.zeros((batch, cfg.rglru.d_conv - 1, w), dtype)}
    elif spec.mixer == "mamba":
        di = cfg.ssm.expand * cfg.d_model
        c["rec"] = {"h": jnp.zeros((batch, di * cfg.ssm.d_state), jnp.float32),
                    "conv": jnp.zeros((batch, cfg.ssm.d_conv - 1, di), dtype)}
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               n_blocks: int = 0, page_size: int = 0) -> Any:
    """Build the zeroed cache pytree matching the model's layer layout.

    ``n_blocks``/``page_size`` > 0 selects the paged (block-pool) layout for
    full-attention layers; everything else stays dense.
    """
    dtype = jnp.dtype(cfg.dtype)
    prefix, pattern, n_rep, rem = cfg.layer_specs()

    def mk(spec):
        return _layer_cache(spec, cfg, batch, max_len, dtype,
                            n_blocks=n_blocks, page_size=page_size)

    cache: dict = {}
    if prefix:
        cache["prefix"] = [mk(s) for s in prefix]
    if n_rep:
        # the stacked leaves are made at their full shape: a layer's leaf
        # broadcast up would hold two more layers' worth while it is copied,
        # which a pool sized to the chip's memory has no room for
        per = jax.eval_shape(lambda: [mk(s) for s in pattern])
        cache["blocks"] = jax.tree.map(
            lambda x: jnp.zeros((n_rep,) + x.shape, x.dtype), per)
    if rem:
        cache["suffix"] = [mk(s) for s in rem]
    return cache


def cache_bytes(cache) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))


def kv_bytes(cache, *, pool_n_blocks: int | None = None) -> int:
    """Bytes of self-attention KV storage (dense buffers + paged pools,
    latent rows included); recurrent states and cross caches excluded.
    With ``pool_n_blocks``, count only the paged pool leaves (those sized
    ``n_blocks`` on their batch-position axis)."""
    total = 0

    def f(path, leaf):
        nonlocal total
        keys = [getattr(k, "key", None) for k in path]
        if "self" not in keys:
            return
        if pool_n_blocks is not None:
            axis = 1 if "blocks" in keys else 0
            if leaf.shape[axis] != pool_n_blocks:
                return
        total += leaf.size * leaf.dtype.itemsize

    jax.tree_util.tree_map_with_path(f, cache)
    return total


def copy_block(cache, src, dst, n_blocks: int):
    """Copy pool block ``src`` into ``dst`` across every paged pool leaf —
    K, V, *and* the per-row quantization scales, which is what lets shared
    quantized pages round-trip exactly through prefix-cache copy-on-write.

    ``src``/``dst`` may be traced scalars (the serve engine jits this with
    the cache donated, so the copy cost is one page's rows, not the pool).
    Non-pool leaves (dense KV, ring buffers, recurrent states, cross
    caches) pass through untouched.
    """

    def f(path, leaf):
        keys = [getattr(k, "key", None) for k in path]
        if "self" not in keys:
            return leaf
        axis = 1 if "blocks" in keys else 0
        if leaf.shape[axis] != n_blocks:
            return leaf
        if axis == 0:
            return leaf.at[dst].set(leaf[src])
        return leaf.at[:, dst].set(leaf[:, src])

    return jax.tree_util.tree_map_with_path(f, cache)


def pages_per_slot(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def default_n_blocks(max_slots: int, max_len: int, page_size: int) -> int:
    """Dense-equivalent pool capacity plus the reserved null block."""
    return max_slots * pages_per_slot(max_len, page_size) + 1


def kv_block_bytes(cfg: ModelConfig, page_size: int) -> int:
    """KV bytes of ONE pool block summed over the paged (global-attention
    and latent-attention) layers, honoring ``cfg.kv_dtype`` — quantized pools count the storage
    width plus the per-row scale overhead (``2 × K`` fp16 scalars per row).
    """
    hd, K = cfg.resolved_head_dim, cfg.n_kv_heads
    kv_bytes_elem = 2 * jnp.dtype(cfg.dtype).itemsize          # K and V
    scale_bytes_row = 0
    if is_quant_dtype(cfg.kv_dtype):
        kv_bytes_elem = 2 * jnp.dtype(canonical_dtype(cfg.kv_dtype)).itemsize
        scale_bytes_row = 2 * jnp.dtype(KV_SCALE_DTYPE).itemsize
    layers = cfg.all_layers()
    n_paged = sum(1 for sp in layers if sp.mixer == "full")
    per_row = K * (hd * kv_bytes_elem + scale_bytes_row)
    n_latent = sum(1 for sp in layers if sp.mixer == "mla")
    latent_row = (latent_width(cfg) * jnp.dtype(cfg.dtype).itemsize
                  if n_latent else 0)
    return page_size * (n_paged * per_row + n_latent * latent_row)


def n_blocks_for_bytes(cfg: ModelConfig, hbm_bytes: int, page_size: int,
                       kv_shard: int = 1) -> int:
    """Pool blocks (null block included) a *per-device* KV-HBM budget
    admits — the precision dividend: int8/fp8 KV roughly doubles/quadruples
    the blocks the same budget holds vs bf16/fp32. ``kv_shard`` (> 1 when a
    serve-mode partitioner shards the pools by KV head over the model axis)
    is the capacity dividend of scale-out: each block costs every device
    only ``1/kv_shard`` of its bytes, so the same per-device budget holds
    ``kv_shard×`` the blocks."""
    per_block = kv_block_bytes(cfg, page_size) // max(kv_shard, 1)
    return max(int(hbm_bytes // max(per_block, 1)), 1) + 1
