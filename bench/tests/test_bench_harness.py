"""CPU tests of the benchmark harness: it finds its parts by name, its
generators and arithmetic are right, and its correctness check fails where
it must. Runs use the tiny configuration under ``data/tiny`` on the CPU,
with the harness's look for a chip skipped."""
from __future__ import annotations

import hashlib
import json
import shutil
import subprocess
import sys
from argparse import Namespace
from pathlib import Path

import numpy as np
import pytest

from bench import arch, costs, model, run, serve, traffic

HERE = Path(__file__).resolve().parent
REPO = HERE.parents[1]
TINY = HERE / "data" / "tiny"


@pytest.fixture
def root(tmp_path):
    """A checkout-like root: the tiny BENCHMARK.json and data files, and
    the real metric readers and architecture modules."""
    shutil.copytree(TINY, tmp_path, dirs_exist_ok=True)
    for d in ("metrics", "arch"):
        shutil.copytree(REPO / "bench" / d, tmp_path / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


#: device memory the tiny cells are sized for: the CPU reports none
TINY_BYTES = 3_000_000


def _run(root, workload, seed, fault=None, seconds=3.0):
    args = Namespace(workload=workload, seed=seed, seconds=seconds, trace=0)
    return run.run(args, root=root, require_chip=False, fault=fault,
                   bytes_limit=TINY_BYTES)


#: an architecture added as a new file: Qwen3 with its embedding scaled by
#: sqrt(hidden_size), as Gemma's is, which the program, the reference and
#: the weights have to agree on
TOY_ARCH = '''"""Qwen3 with its embedding scaled by sqrt(hidden_size)."""
import dataclasses

import jax.numpy as jnp

from bench.arch import qwen3

weight_shapes = qwen3.weight_shapes
to_program = qwen3.to_program
shape = qwen3.shape


def program_config(c):
    return dataclasses.replace(qwen3.program_config(c), embed_scale=True)


def hidden(w, c, tokens, quant=False):
    scale = c["hidden_size"] ** 0.5
    return qwen3.hidden(dict(w, embed=w["embed"].astype(jnp.float32) * scale),
                        c, tokens, quant)
'''


# ---- found by name ---------------------------------------------------------
def test_new_config_traffic_and_metric_are_found_as_new_files(root):
    """A configuration, a traffic mix, a per-layer metric and an
    architecture added as new files plus new entries in BENCHMARK.json; no
    existing file edited. A cell of the new architecture (untied, its
    embedding scaled) runs to ``correct``; a configuration whose
    ``model_type`` has no module fails and names the modules there are."""
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg["name"] = "tiny-wide"
    cfg["intermediate_size"] = 256
    (root / "bench/configs/tiny-wide.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "bench/traffic/tchat.json").read_text())
    mix["rate_per_s"] = 7.0
    (root / "bench/traffic/tburst.json").write_text(json.dumps(mix))
    (root / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx.counters['decode_steps']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-wide", "source": "test",
                             "file": "bench/configs/tiny-wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide.burst",
                               "config": "tiny-wide", "traffic": "tburst",
                               "chips": 1, "why": "test"})
    toy = dict(json.loads((root / "bench/configs/tiny.json").read_text()),
               name="toy", model_type="toyscale", tie_word_embeddings=False)
    (root / "bench/configs/toy.json").write_text(json.dumps(toy))
    (root / "bench/arch/toyscale.py").write_text(TOY_ARCH)
    (root / "bench/limits/toy.chat.json").write_text(
        (root / "bench/limits/tiny.chat.json").read_text())
    none = dict(toy, name="none", model_type="nonesuch")
    (root / "bench/configs/none.json").write_text(json.dumps(none))
    for name in ("toy", "none"):
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": f"{name}.chat", "config": name,
                                   "traffic": "tchat", "chips": 1,
                                   "why": "test"})
    bench["per_layer"].append({"name": "new_metric", "unit": "steps",
                               "better": "higher",
                               "source": "program_counter", "layer": "x",
                               "moves": "output_tokens_per_s",
                               "workloads": ["tiny-wide.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = run.load_cell(root, "tiny-wide.burst")
    assert cell.config["intermediate_size"] == 256
    assert cell.traffic["rate_per_s"] == 7.0
    assert [m["name"] for m in cell.per_layer][-1] == "new_metric"
    assert "new_metric" not in [m["name"] for m in
                                run.load_cell(root, "tiny.chat").per_layer]
    read = run.load_reader(root, "new_metric")
    assert read(Namespace(counters={"decode_steps": 3})) == 6.0
    assert cell.arch.program_config(cell.config).d_ff == 256
    assert all(p.read_bytes() == b for p, b in before.items()
               if p.name != "BENCHMARK.json")

    toy_cell = run.load_cell(root, "toy.chat")
    a = toy_cell.arch
    assert Path(a.__file__) == root / "bench/arch/toyscale.py"
    assert a.program_config(toy).embed_scale
    w = model.make_weights(a.weight_shapes(toy), 3)
    assert "lm_head" in w
    tokens = np.arange(40) % toy["vocab_size"]
    qwen3 = arch.load(toy | {"model_type": "qwen3"})
    assert not np.allclose(a.hidden(w, toy, tokens),
                           qwen3.hidden(w, toy, tokens), atol=0.1)
    out = _run(root, "toy.chat", 5)
    assert out["correct"], out["checks"]
    assert out["checks"]["tokens_compared"]["value"] >= 192

    with pytest.raises(KeyError, match=r"'nonesuch'.*known: \['llama', "
                                       r"'qwen3', 'toyscale'\]"):
        run.load_cell(root, "none.chat")


# ---- generators --------------------------------------------------------------
def _mix(name, seed, seconds=30.0, clients=0):
    where = REPO if (REPO / "bench/traffic" / f"{name}.json").exists() else TINY
    params = json.loads((where / "bench/traffic" / f"{name}.json")
                        .read_text())
    return params, traffic.generate(params, seed, seconds, 1000,
                                    clients=clients)


@pytest.mark.parametrize("name", ["tchat", "long-decode", "tagent"])
def test_generator_is_deterministic_per_seed(name):
    _, a = _mix(name, 2**31 + 5, clients=4)
    _, b = _mix(name, 2**31 + 5, clients=4)
    _, c = _mix(name, 2**31 + 6, clients=4)
    assert [x.max_new for x in a.items] == [x.max_new for x in b.items]
    assert all(np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.items, b.items))
    assert [x.due for x in a.items] == [x.due for x in b.items]
    # another seed: other tokens, the same schedule
    assert [x.max_new for x in a.items] == [x.max_new for x in c.items]
    assert [len(x.prompt) for x in a.items] == [len(x.prompt)
                                                for x in c.items]
    assert [x.due for x in a.items] == [x.due for x in c.items]
    assert not np.array_equal(a.items[0].prompt, c.items[0].prompt)
    # shuffled, not in quantile order
    lens = [x.max_new for x in a.items]
    assert lens != sorted(lens)


@pytest.mark.parametrize("name", ["tchat", "long-decode", "tagent"])
def test_generator_honours_its_parameters(name):
    params, m = _mix(name, 7, seconds=40.0, clients=5)
    sp = params.get("shared_prefix") or {}
    pre = int(sp.get("tokens", 0))
    fresh = np.array([len(x.prompt) - (pre if x.prefix >= 0 else 0)
                      for x in m.items])
    out = np.array([x.max_new for x in m.items])
    assert fresh.min() >= params["prompt"]["min"]
    assert fresh.max() <= params["prompt"]["max"]
    assert out.min() >= params["output"]["min"]
    assert out.max() <= params["output"]["max"]
    if params["prompt"]["dist"] == "lognormal":
        assert abs(np.median(fresh) - params["prompt"]["median"]) < (
            0.05 * params["prompt"]["median"])
    if params["loop"] == "open":
        due = np.array([x.due for x in m.items])
        assert len(m.items) == int(params["rate_per_s"] * 40.0)
        assert due[0] == 0.0 and due.max() < 40.0
        assert np.all(np.diff(due) >= 0)
        gaps = np.diff(due)
        assert abs(gaps.mean() * params["rate_per_s"] - 1) < 0.05
        # exponential gaps: coefficient of variation near 1
        assert 0.8 < gaps.std() / gaps.mean() < 1.2
    if sp:
        picks = np.bincount([x.prefix for x in m.items],
                            minlength=sp["count"])
        w = 1 / np.arange(1, sp["count"] + 1) ** sp["zipf_s"]
        assert np.abs(picks - len(m.items) * w / w.sum()).max() <= 1
        for x in m.items:
            assert np.array_equal(x.prompt[:pre], m.prefixes[x.prefix])


# ---- arithmetic -----------------------------------------------------------------
def _shape(name):
    c = json.loads((REPO / "bench/configs" / f"{name}.json").read_text())
    return arch.load(c).shape(c)


#: the sizes of Qwen/Qwen3-0.6B's published config.json
QWEN3_06B = {"hidden_size": 1024, "num_hidden_layers": 28, "head_dim": 128,
             "num_attention_heads": 16, "num_key_value_heads": 8,
             "intermediate_size": 3072, "vocab_size": 151936,
             "tie_word_embeddings": True, "model_type": "qwen3"}


def test_qwen3_counts_by_hand():
    s = arch.load(QWEN3_06B).shape(QWEN3_06B)
    per_layer = 1024 * 128 * (16 + 8 + 8 + 16) + 3 * 1024 * 3072
    assert s.params == 28 * per_layer + 151936 * 1024 + 28 * 2304 + 1024
    assert s.params == 596_049_920
    assert s.kv_token_bytes == 28 * 2 * 8 * 128 * 2 == 114_688
    assert s.token_flops(100, logits=True) == (
        2 * 28 * per_layer + 4 * 28 * 16 * 128 * 100 + 2 * 1024 * 151936)
    # prefill of positions 0..9: attention over 1 + 2 + ... + 10 rows
    assert s.prefill_flops(0, 10) == (
        2 * 28 * per_layer * 10 + 4 * 28 * 16 * 128 * 55
        + 2 * 1024 * 151936)
    assert s.gemm(8, 1024, 3072) == (
        2 * 8 * 1024 * 3072, 2 * (8 * 1024 + 1024 * 3072 + 8 * 3072))
    assert s.context_flops(100) == 4 * 28 * 16 * 128 * 100


def test_deepseek_l15_counts_by_hand():
    s = _shape("deepseek-7b-l15")
    per_layer = 4096 * 128 * 128 + 3 * 4096 * 11008
    assert not s.tied
    assert s.params == (15 * per_layer + 2 * 102400 * 4096
                        + 15 * 2 * 4096 + 4096)
    assert s.weight_bytes == 7_749_230_592
    assert s.kv_token_bytes == 15 * 2 * 32 * 128 * 2 == 245_760
    assert s.paged_attention(1000) == (4 * 1000 * 32 * 128,
                                       1000 * 2 * 32 * 128 * 2)
    assert s.context_flops(1000) == 4 * 15 * 32 * 128 * 1000


#: what the harness drew and counted before the architectures became
#: modules of their own (``bench/arch/``): the same names, the same key
#: split, the same arrays and the same formulas
TINY_WEIGHTS_SHA256 = ("df810276fc9f7922e0d68cbda68d7a480fada309dc6358f73d"
                       "92902427cb40b5")
DEEPSEEK_L15_SHAPES = {
    "embed": (102400, 4096), "final_norm": (4096,),
    "attn_norm": (15, 4096), "mlp_norm": (15, 4096),
    "wq": (15, 4096, 4096), "wk": (15, 4096, 4096), "wv": (15, 4096, 4096),
    "wo": (15, 4096, 4096), "w_gate": (15, 4096, 11008),
    "w_up": (15, 4096, 11008), "w_down": (15, 11008, 4096),
    "lm_head": (4096, 102400)}
STEP_MFU = 3.3227313588629444


def test_weights_of_a_seed_are_pinned():
    c = json.loads((TINY / "bench/configs/tiny.json").read_text())
    w = model.make_weights(arch.load(c).weight_shapes(c), 3)
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(k.encode())
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == TINY_WEIGHTS_SHA256


def test_deepseek_weight_shapes_are_pinned():
    c = json.loads((REPO / "bench/configs/deepseek-7b-l15.json").read_text())
    assert arch.load(c).weight_shapes(c) == DEEPSEEK_L15_SHAPES


def test_step_mfu_is_pinned():
    """The reader on a fixed context: decode over 24 stepped sequences and
    60,000 context rows, and one of two prompts whose first token came
    inside the window."""
    c = json.loads((REPO / "bench/configs/deepseek-7b-l15.json").read_text())
    ctx = run.Context(
        trace=None, lo=0, hi=2_000_000_000,
        counters={"decode_window_batch": 24, "decode_window_kv_rows": 60_000},
        shape=arch.load(c).shape(c), peaks=costs.peaks("TPU v5 lite"),
        engine={"max_slots": 3, "max_len": 4096, "chunk": 256, "page": 16,
                "n_blocks": 975},
        requests=[{"prompt": 2042, "max_new": 700, "prefix": -1,
                   "tokens_ts": [1.0, 1.1]},
                  {"prompt": 1500, "max_new": 600, "prefix": -1,
                   "tokens_ts": [0.2, 0.3]}],
        chips=1, extra={"t0": 0.5, "t1": 2.0, "prefix_tokens": 0})
    assert run.load_reader(REPO, "step_mfu")(ctx) == STEP_MFU


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peak table entry"):
        costs.peaks("TPU v9 imaginary")
    assert costs.peaks("TPU v5 lite").bf16_flops == 197e12


def test_configs_match_published_sizes():
    d = json.loads((REPO / "bench/configs/deepseek-7b-l15.json").read_text())
    assert (d["hidden_size"], d["num_attention_heads"],
            d["num_key_value_heads"], d["intermediate_size"],
            d["vocab_size"]) == (4096, 32, 32, 11008, 102400)
    assert d["tie_word_embeddings"] is False
    assert d["num_hidden_layers"] == 15 and d["reduced"] == [
        "num_hidden_layers"]
    cfg = arch.load(d).program_config(d)
    assert not cfg.tie_embeddings and cfg.n_layers == 15
    assert cfg.param_dtype == "bfloat16"


def test_pool_sizing_fills_memory():
    """The pool is the largest whose steps' need, affine in the pool, fits
    the chip's memory less the headroom; each closed-loop client holds the
    largest reservation."""
    c = json.loads((REPO / "bench/configs/deepseek-7b-l15.json").read_text())
    mix = json.loads((REPO / "bench/traffic/long-decode.json").read_text())
    s = arch.load(c).shape(c)
    limit, work = 16_909_336_064, 300_000_000
    probed = []

    def need(sz):
        probed.append(sz.n_blocks)
        return s.weight_bytes + work + 2 * sz.pool_bytes

    sz = serve.size_pool(s, c, mix, limit, need)
    assert len(probed) == 2
    budget = limit * (1 - serve.HEADROOM)
    block = 16 * s.kv_token_bytes
    assert budget - 2 * block < need(sz) <= budget
    assert sz.need_bytes == need(sz)
    assert sz.clients == sz.max_slots == (sz.n_blocks - 1) // 256


def test_step_bytes_grow_with_the_pool():
    """The compiler's memory_analysis of the engine's own decode and prefill
    steps, at the tiny size on the CPU: a larger pool needs more."""
    c = json.loads((TINY / "bench/configs/tiny.json").read_text())
    mix = json.loads((TINY / "bench/traffic/tclosed.json").read_text())
    a = arch.load(c)
    s = a.shape(c)
    cfg = a.program_config(c)
    params = a.to_program(model.make_weights(a.weight_shapes(c), 1), c)
    got = []
    for n in (64, 128):
        sz = serve.at_blocks(s, c, mix, n)
        probe = serve.make_engine(cfg, c, params, serve.Sizing(
            n_blocks=sz.max_slots + 1, max_slots=sz.max_slots, clients=0,
            pool_bytes=0), 0)
        got.append(serve.step_bytes(probe, params, sz))
        assert got[-1] > s.weight_bytes + sz.pool_bytes
    assert got[1] > got[0]


# ---- the reference and the check ------------------------------------------------
def test_reference_agrees_with_engine_at_tiny_size(root):
    out = _run(root, "tiny.chat", 5)
    assert out["correct"], out["checks"]
    # bf16 serving against the float32 reference: a near-tie can flip, the
    # gap stays at bf16 rounding of logits of order 1
    assert 0 < out["checks"]["mean_gap"]["value"] < 0.002
    assert out["checks"]["tokens_compared"]["value"] >= 192
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"output_tokens_per_s",
                                   "itl_p95_ms", "setup_s"}


def test_closed_loop_and_prefix_cells_run(root):
    out = _run(root, "tiny.closed", 6)
    assert out["correct"], out["checks"]
    out = _run(root, "tiny.agent", 7)
    assert out["correct"], out["checks"]


def test_closed_loop_opens_in_its_steady_state(root):
    """Set-up starts every client part-way through its first request: the
    prompts are prefilled, and the outputs still to come are staggered, so
    requests finish, and their clients send the next, inside the window."""
    cell = run.load_cell(root, "tiny.closed")
    devices = run._chip_check(cell, require_chip=False)
    su = run.set_up(cell, 9, 3.0, devices, bytes_limit=TINY_BYTES)
    C = su.sizing.clients
    assert C >= 4 and len(su.primed) == C
    left = [r.max_new for r in su.primed]
    full = [it.max_new for it in su.mix.items[:C]]
    assert left == [max(1, round(f * (i + 0.5) / C))
                    for i, f in enumerate(full)]
    assert all(su.engine.results[r.uid].tokens for r in su.primed)
    win = serve.run_window(su.engine, su.mix, 3.0, C, primed=su.primed)
    e2e = serve.end_to_end(su.engine, win, open_loop=False)
    assert e2e["finished"] >= 1 and e2e["attempted"] > C


def test_altered_token_is_not_correct(root):
    """A token altered where it is produced: every fifth emitted token is
    replaced by its neighbour in the vocabulary."""
    def fault(engine):
        emit, n = engine._emit, [0]

        def altered(slot, tok):
            n[0] += 1
            emit(slot, (tok + 1) % engine.cfg.vocab_size
                 if n[0] % 5 == 0 else tok)
        engine._emit = altered

    out = _run(root, "tiny.chat", 5, fault=fault)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > 0.002


def test_step_that_keeps_its_state_is_not_correct(root):
    """A decode step that returns the KV cache unchanged: later tokens
    attend without the rows of the tokens before them."""
    import jax

    def fault(engine):
        step = engine._decode_all
        engine._decode_fn = jax.jit(
            lambda p, cache, *a: (step(p, cache, *a)[0], cache))

    out = _run(root, "tiny.chat", 5, fault=fault)
    assert not out["correct"]
    assert out["checks"]["mean_gap"]["value"] > 0.002


@pytest.mark.parametrize("kind", ["reference", "program"])
def test_int8_control_reads_wider_than_the_program(kind):
    """The control, int8 weights teacher-forced over the tokens the program
    served (the reference with its weights rounded, as the DeepSeek cell
    runs it, or the program's own int8 path), comes out not correct through
    the harness's verdict, and the bf16 program correct, on every seed. At
    this size (vocabulary 8,192, width 128; seeds 5-12) the program's mean
    gap read 0 to 1.26e-4, the reference's at int8 4.56e-4 to 1.75e-3,
    the program's int8 path 6.22e-4 to 1.70e-3: the limit is 3e-4. The
    requests run to completion outside any window, so the served tokens do
    not depend on the host's speed."""
    from bench import check, control

    c = json.loads((TINY / "bench/configs/tinyv.json").read_text())
    limits = check.load_limits(TINY, "tinyv.control")
    a = arch.load(c)
    cfg = a.program_config(c)
    for seed in (5, 6, 7, 8):
        rng = np.random.default_rng(seed)
        w = model.make_weights(a.weight_shapes(c), seed)
        params = a.to_program(w, c)
        engine = serve.make_engine(cfg, c, params, serve.Sizing(
            n_blocks=64, max_slots=4, clients=0, pool_bytes=0), seed)
        prompts = [rng.integers(0, c["vocab_size"], n) for n in (40, 70)]
        for i, p in enumerate(prompts):
            engine.submit(serve._request(i, p, 96))
        assert serve.drain(engine, [0, 1], 120.0)
        samples = [(p, engine.results[i].tokens)
                   for i, p in enumerate(prompts)]
        out = control.judge(a, w, c, samples, limits, kind, params=params,
                            cfg=cfg)
        assert out["program"]["correct"], (seed, out)
        assert not out["control"]["correct"], (seed, out)
        assert out["control"]["checks"]["tokens_compared"] == 192


# ---- the command ---------------------------------------------------------------
def test_no_chip_prints_nothing_and_fails(tmp_path):
    p = subprocess.run(
        [sys.executable, str(REPO / "bench/run.py"), "--workload",
         "deepseek-7b-l15.long-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert p.returncode == run.EXIT_NO_CHIP, p.stderr[-2000:]
    assert p.stdout.strip() == ""


def test_bare_benchmark_directory_fails(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".out", "__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "deepseek-7b-l15.long-decode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)})
    assert p.returncode == run.EXIT_BAD_TREE, p.stderr[-2000:]
    assert p.stdout.strip() == ""
