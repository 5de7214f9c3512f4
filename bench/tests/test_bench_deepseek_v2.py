"""CPU tests of the DeepSeek-V2 architecture module (``bench/arch/
deepseek_v2.py``) on a tiny configuration: the program's served tokens
stay within the limits of the float32 reference, the int8 control and a
planted fault do not, the reference follows the published YaRN formulas,
and the cost counts are pinned by hand."""
from __future__ import annotations

import json
import math
import shutil
from argparse import Namespace

import numpy as np
import pytest

from bench import arch, check, control, model, run, serve
from bench.tests.test_bench_harness import REPO, TINY

#: DeepSeek-V2-Lite's keys at a tiny width: the same kinds of layer (one
#: dense, then experts with shared ones), YaRN over an original context
#: shorter than the prompts, so that positions beyond it are interpolated
TINY_DS = {
    "name": "tinyds", "source": "test", "deployment": "test",
    "reduced": [], "assumed": {}, "program_arch": "deepseek-v2-lite",
    "engine": {"page_size": 16, "prefill_chunk": 32, "max_len": 512,
               "prefix_cache": True, "kernel_backend": "auto"},
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 256, "intermediate_size": 256,
    "kv_lora_rank": 32, "max_position_embeddings": 4096,
    "model_type": "deepseek_v2", "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 8,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "greedy",
    "torch_dtype": "bfloat16", "v_head_dim": 16, "vocab_size": 8192}

#: the tiny cell's limit. Teacher-forced over two prompts of 90 and 130
#: tokens and 96 served tokens each, seeds 3-12, the bf16 program read mean
#: gaps of 4.0e-4 to 3.57e-3 against the float32 reference (at this width
#: a near-tie in the top-k routing flips now and then, and moves every
#: later token) and the int8 control 4.15e-3 to 8.96e-3 (my CPU runs). The
#: cell on seed 3 read 6.3e-4, with YaRN off 1.14 and with the top-k
#: weights renormalised 0.047
TINY_DS_LIMITS = {"mean_gap": 4e-3}
#: device memory the tiny cell is sized for: the CPU reports none
TINY_DS_BYTES = 24_000_000


@pytest.fixture
def root(tmp_path):
    """The tiny checkout of the harness tests with the tiny DeepSeek-V2
    cell added as new files and entries."""
    shutil.copytree(TINY, tmp_path, dirs_exist_ok=True)
    for d in ("metrics", "arch"):
        shutil.copytree(REPO / "bench" / d, tmp_path / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/configs/tinyds.json").write_text(json.dumps(TINY_DS))
    (tmp_path / "bench/limits/tinyds.closed.json").write_text(
        json.dumps(TINY_DS_LIMITS))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tinyds", "source": "test",
                             "file": "bench/configs/tinyds.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tinyds.closed", "config": "tinyds",
                               "traffic": "tclosed", "chips": 1,
                               "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def _run(root, seed, fault=None):
    args = Namespace(workload="tinyds.closed", seed=seed, seconds=8.0,
                     trace=0)
    return run.run(args, root=root, require_chip=False, fault=fault,
                   bytes_limit=TINY_DS_BYTES)


def test_program_is_within_the_reference_limits(root):
    out = _run(root, 3)
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 192


@pytest.mark.parametrize("fault", ["yarn_off", "renormalised_top_k"])
def test_planted_fault_is_not_correct(root, fault):
    """The program told to drop YaRN (plain RoPE, softmax scale without
    mscale²), or to renormalise the top-k weights the published config
    leaves as they are: both read above the limit."""
    import dataclasses

    def plant(engine):
        cfg = engine.cfg
        if fault == "yarn_off":
            cfg = cfg.replace(rope_scaling=None)
        else:
            cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                      renorm_topk=True))
        engine.cfg = cfg

    out = _run(root, 3, fault=plant)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > TINY_DS_LIMITS["mean_gap"]


def test_int8_control_is_not_correct():
    """The reference with its weights rounded to int8, teacher-forced over
    the tokens the program served, fails the limit the program passes."""
    a = arch.load(TINY_DS)
    cfg = a.program_config(TINY_DS)
    for seed in (7, 10):
        rng = np.random.default_rng(seed)
        w = model.make_weights(a.weight_shapes(TINY_DS), seed)
        params = a.to_program(w, TINY_DS)
        model.check_program_layout(params, cfg)
        engine = serve.make_engine(cfg, TINY_DS, params, serve.Sizing(
            n_blocks=64, max_slots=4, clients=0, pool_bytes=0), seed)
        prompts = [rng.integers(0, TINY_DS["vocab_size"], n)
                   for n in (90, 130)]
        for i, p in enumerate(prompts):
            engine.submit(serve._request(i, p, 96))
        assert serve.drain(engine, [0, 1], 300.0)
        samples = [(p, engine.results[i].tokens)
                   for i, p in enumerate(prompts)]
        out = control.judge(a, w, TINY_DS, samples, TINY_DS_LIMITS,
                            "reference")
        assert out["program"]["correct"], (seed, out)
        assert not out["control"]["correct"], (seed, out)


def test_yarn_follows_the_published_formulas():
    """cos/sin against a direct transcription of ``yarn_find_correction_
    range``, ``yarn_linear_ramp_mask`` and ``yarn_get_mscale`` at
    DeepSeek-V2-Lite's sizes (dim 64, factor 40 over 4,096), and the
    softmax scale ``192^-0.5 · (0.1·0.707·ln 40 + 1)^2``."""
    a = arch.load(TINY_DS)
    c = json.loads((REPO / "bench/configs/deepseek-v2-lite-l7.json")
                   .read_text())
    dim, base = 64, 10000.0
    low = math.floor(dim * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(base)))
    high = math.ceil(dim * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(base)))
    assert (low, high) == (10, 23)
    i = np.arange(32)
    extra = base ** (-2.0 * i / dim)
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    inv = extra / 40 * (1 - mask) + extra * mask
    pos = np.array([0, 1, 4095, 4096, 16383])
    cos, sin = a.yarn_cos_sin(c, 16384)
    want = np.concatenate([np.outer(pos, inv)] * 2, axis=1)
    np.testing.assert_allclose(np.asarray(cos)[pos], np.cos(want),
                               rtol=0, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin)[pos], np.sin(want),
                               rtol=0, atol=2e-3)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert a.softmax_scale(c) == pytest.approx(192 ** -0.5 * m * m)


def test_counts_by_hand():
    """DeepSeek-V2-Lite at 7 layers: 4,009,526,784 parameters (embedding
    and head 419.4 M, the dense layer 81.0 M, each expert layer 584.8 M),
    8,064 B of latent cache per token, and the counts of the readers."""
    c = json.loads((REPO / "bench/configs/deepseek-v2-lite-l7.json")
                   .read_text())
    s = arch.load(c).shape(c)
    attn = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    norms = 2 * 2048 + 512
    dense = attn + 3 * 2048 * 10944 + norms
    moe = (attn + 64 * 3 * 2048 * 1408 + 3 * 2048 * 2816 + 2048 * 64
           + norms)
    assert (dense, moe) == (81_007_104, 584_847_872)
    assert s.params == 2 * 102400 * 2048 + dense + 6 * moe + 2048
    assert s.params == 4_009_526_784
    assert s.weight_bytes == 8_019_053_568
    assert s.kv_token_bytes == 7 * 576 * 2 == 8064
    assert s.mla_attention(1000) == (1000 * 16 * (576 + 512) * 2,
                                     1000 * 1152)
    active = 2048 * 3 * 1408 * 6 + 3 * 2048 * 2816 + 2048 * 64
    per_token = 2 * (7 * attn + 3 * 2048 * 10944 + 6 * active)
    assert s.token_flops(100, logits=True) == (
        per_token + 2 * 16 * (192 + 128) * 7 * 100 + 2 * 2048 * 102400)
    assert s.prefill_flops(0, 10) == (
        10 * per_token + 2 * 16 * 320 * 7 * 55 + 2 * 2048 * 102400)


def test_config_is_the_catalog_entry_cut_in_depth():
    c = json.loads((REPO / "bench/configs/deepseek-v2-lite-l7.json")
                   .read_text())
    assert c["reduced"] == ["num_hidden_layers"]
    assert c["num_hidden_layers"] == 7
    assert (c["hidden_size"], c["kv_lora_rank"], c["n_routed_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["vocab_size"]) == (2048, 512, 64, 6, 1408, 102400)
    cfg = arch.load(c).program_config(c)
    assert cfg.n_layers == 7 and not cfg.tie_embeddings
    assert [s.mlp for s in cfg.all_layers()] == ["dense"] + ["moe"] * 6
    assert all(s.mixer == "mla" for s in cfg.all_layers())
    assert cfg.moe.renorm_topk is False and cfg.moe.shared_hidden == 2816
    assert cfg.rope_scaling.factor == 40.0


def test_latent_readers_read_nothing_without_their_sources():
    """On a run of a program without latent attention or expert counters
    (the 7B cell, or a parent commit), the new readers return None."""
    c = json.loads((REPO / "bench/configs/deepseek-7b-l15.json").read_text())

    class NoOps:
        ops = []

    class Trace:
        devices = {"d0": NoOps()}

        def device_names(self):
            return ["d0"]

    ctx = run.Context(trace=Trace(), lo=0, hi=10, counters={
        "decode_window_kv_rows": 100}, shape=arch.load(c).shape(c),
        peaks=None, engine={}, requests=[], chips=1)
    assert run.load_reader(REPO, "mla_attention_roofline")(ctx) is None
    assert run.load_reader(REPO, "moe_row_use_share")(ctx) is None
    ctx.counters.update(moe_rows_routed=30, moe_rows_computed=40)
    assert run.load_reader(REPO, "moe_row_use_share")(ctx) == 75.0
