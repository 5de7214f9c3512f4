"""Back-compat aggregator — canonical definitions live in the per-arch modules
(one ``configs/<id>.py`` per assigned architecture) and ``registry.py``."""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCHS, get_arch  # noqa: F401


def reduced(cfg: ModelConfig, *, n_layers: int | None = None) -> ModelConfig:
    """Small same-family config for CPU smoke tests."""
    per = len(cfg.pattern)
    nl = n_layers if n_layers is not None else len(cfg.prefix) + 2 * per
    kw: dict = dict(
        name=cfg.name + "-smoke",
        n_layers=nl,
        d_model=128,
        n_heads=4 if cfg.n_heads else 0,
        n_kv_heads=max(1, cfg.n_kv_heads * 4 // max(cfg.n_heads, 1)) if cfg.n_heads else 0,
        head_dim=32 if cfg.head_dim else 0,
        d_ff=256 if cfg.d_ff else 0,
        vocab_size=512,
        window=min(cfg.window, 64) if cfg.window else 0,
        attn_chunk=64,
        max_position=4096,
        loss_chunk=min(cfg.loss_chunk, 64) if cfg.loss_chunk else 0,
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, n_experts=8,
            top_k=min(cfg.moe.top_k, 2),
            d_expert=64,
            d_shared=128 if cfg.moe.d_shared else 0,
            n_shared=min(cfg.moe.n_shared, 2))
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=64, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=32)
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(cfg.ssm, d_state=8, dt_rank=16, chunk=16)
    if cfg.rglru is not None:
        kw["rglru"] = dataclasses.replace(cfg.rglru, lru_width=128, chunk=16)
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder, n_layers=2, n_frames=32)
    if cfg.n_frontend_tokens:
        kw["n_frontend_tokens"] = 16
    return cfg.replace(**kw)
