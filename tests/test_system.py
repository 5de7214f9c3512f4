"""End-to-end behaviour tests for the paper's system (integration level)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCHS, SHAPES, SKIPS, all_cells, get_arch,
                           get_shape, is_skipped, reduced, strategy)


def test_assignment_coverage():
    """Exactly the 11 registered archs (the assigned 10 and DeepSeek-V2-Lite)
    x 4 shapes; skips only where the assignment allows (long_500k on
    full-attention archs)."""
    assert set(ARCHS) == {
        "gemma2-27b", "deepseek-7b", "minicpm-2b", "qwen3-0.6b",
        "recurrentgemma-2b", "whisper-tiny", "llava-next-mistral-7b",
        "qwen2-moe-a2.7b", "deepseek-moe-16b", "falcon-mamba-7b",
        "deepseek-v2-lite"}
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    # long_500k runs for sub-quadratic archs only
    runs_long = [a for a in ARCHS if not is_skipped(a, "long_500k")]
    assert set(runs_long) == {"gemma2-27b", "recurrentgemma-2b",
                              "falcon-mamba-7b"}
    assert all(s == "long_500k" for (_, s) in SKIPS)
    assert len(all_cells(include_skipped=True)) == 44


def test_assigned_dims_exact():
    """Spot-check the exact assigned dimensions (no drift)."""
    g = get_arch("gemma2-27b")
    assert (g.n_layers, g.d_model, g.n_heads, g.n_kv_heads, g.d_ff,
            g.vocab_size) == (46, 4608, 32, 16, 36864, 256000)
    f = get_arch("falcon-mamba-7b")
    assert (f.n_layers, f.d_model, f.vocab_size, f.ssm.d_state) == \
        (64, 4096, 65024, 16)
    d = get_arch("deepseek-moe-16b")
    assert (d.n_layers, d.d_model, d.moe.n_experts, d.moe.top_k) == \
        (28, 2048, 64, 6)
    q = get_arch("qwen2-moe-a2.7b")
    assert (q.n_layers, q.moe.n_experts, q.moe.top_k) == (24, 60, 4)
    w = get_arch("whisper-tiny")
    assert (w.n_layers, w.d_model, w.encoder.n_layers) == (4, 384, 4)
    r = get_arch("recurrentgemma-2b")
    assert (r.n_layers, r.d_model, r.n_kv_heads) == (26, 2560, 1)
    m = get_arch("minicpm-2b")
    assert (m.n_layers, m.d_model, m.n_heads, m.vocab_size) == \
        (40, 2304, 36, 122753)
    v = get_arch("deepseek-v2-lite")
    assert (v.n_layers, v.d_model, v.n_heads, v.d_ff, v.vocab_size) == \
        (27, 2048, 16, 10944, 102400)
    assert (v.mla.kv_lora_rank, v.mla.qk_nope_head_dim,
            v.mla.qk_rope_head_dim, v.mla.v_head_dim) == (512, 128, 64, 128)
    assert (v.moe.n_experts, v.moe.top_k, v.moe.shared_hidden,
            v.moe.renorm_topk) == (64, 6, 2816, False)


def test_shapes_exact():
    t = get_shape("train_4k")
    assert (t.seq_len, t.global_batch, t.kind) == (4096, 256, "train")
    p = get_shape("prefill_32k")
    assert (p.seq_len, p.global_batch, p.kind) == (32768, 32, "prefill")
    d = get_shape("decode_32k")
    assert (d.seq_len, d.global_batch, d.kind) == (32768, 128, "decode")
    l = get_shape("long_500k")
    assert (l.seq_len, l.global_batch, l.kind) == (524288, 1, "decode")


def test_layer_patterns():
    """Family-defining layer layouts."""
    g = get_arch("gemma2-27b")
    assert [s.mixer for s in g.pattern] == ["local", "full"]
    r = get_arch("recurrentgemma-2b")
    assert [s.mixer for s in r.pattern] == ["rglru", "rglru", "local"]
    assert len(r.all_layers()) == 26
    f = get_arch("falcon-mamba-7b")
    assert all(s.mixer == "mamba" for s in f.all_layers())
    d = get_arch("deepseek-moe-16b")
    layers = d.all_layers()
    assert layers[0].mlp == "dense" and all(s.mlp == "moe"
                                            for s in layers[1:])


def test_e2e_training_learns_tiny():
    """A reduced model must actually learn the synthetic Markov stream."""
    from repro.configs.base import ShapeConfig
    from repro.optim.optimizers import adamw
    from repro.train.trainer import Trainer, TrainerConfig
    import tempfile

    cfg = reduced(get_arch("deepseek-7b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
        d_ff=128, vocab_size=128)
    shape = ShapeConfig("t", "train", seq_len=64, global_batch=8)
    with tempfile.TemporaryDirectory() as d:
        tcfg = TrainerConfig(steps=25, ckpt_dir=d, ckpt_every=100, seed=0)
        out = Trainer(cfg, shape, strategy("ramora"), adamw(3e-3), tcfg).train()
    losses = out["losses"]
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_strategies_are_distinct():
    occ, ram, ogo = strategy("occamy"), strategy("ramora"), strategy("ogopogo")
    assert not occ.fsdp and not occ.tensor_parallel
    assert ram.fsdp and ram.tensor_parallel and not ram.hierarchical_collectives
    assert ogo.multi_pod and ogo.hierarchical_collectives and ogo.chunked_loss
    assert occ.mesh_axes == ("data", "model")
    assert ogo.mesh_axes == ("pod", "data", "model")
