"""Roofline machinery: HLO collective parsing, cost analysis, model FLOPs."""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.roofline import (_shape_bytes, analyze_costs, model_flops,
                                 parse_collectives)
from repro.core.topology import (CHIP, chip_spec, dtype_peak_flops,
                                  roofline_time)

HLO = """
ENTRY main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ag = f32[64,128]{1,0} all-gather(%p0), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = bf16[1024]{0} all-reduce(%x), replica_groups={{0,1},{2,3}}, to_apply=%add
  %rs = f32[4,32]{1,0} reduce-scatter(%y), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = u8[100]{0} collective-permute(%z), source_target_pairs={{0,1}}
  %a2a = f32[8,8]{1,0} all-to-all(%w), replica_groups={{0,1,2,3}}
  %ars = f32[64]{0} all-reduce-start(%q), replica_groups={}
  %ard = f32[64]{0} all-reduce-done(%ars)
  ROOT %t = tuple(%ag)
}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[16,128]") == 16 * 128 * 4
    assert _shape_bytes("bf16[10]") == 20
    assert _shape_bytes("u8[7]") == 7
    assert _shape_bytes("f32[]") == 4  # scalar
    assert _shape_bytes("(f32[4], s8[8])") == 24  # tuples summed


def test_parse_collectives_kinds_and_bytes():
    out = parse_collectives(HLO)
    bk = out["bytes_by_kind"]
    assert bk["all-gather"] == 64 * 128 * 4
    assert bk["all-reduce"] == 1024 * 2 + 64 * 4  # ar + ar-start (done skipped)
    assert bk["reduce-scatter"] == 4 * 32 * 4
    assert bk["collective-permute"] == 100
    assert bk["all-to-all"] == 8 * 8 * 4
    assert out["count_by_kind"]["all-reduce"] == 2
    assert out["total_bytes"] == sum(bk.values())


def test_async_done_not_double_counted():
    out = parse_collectives(HLO)
    # only the -start of the async pair contributes
    assert out["count_by_kind"]["all-reduce"] == 2


def test_analyze_costs_bottleneck():
    r = analyze_costs(flops_per_dev=197e12, bytes_per_dev=1e9,
                      collective_bytes_per_dev=1e9,
                      collectives={}, arch="qwen3-0.6b", shape="train_4k",
                      n_chips=256)
    roof = r["roofline"]
    # 1s compute vs ~1.2ms memory vs 20ms collective
    assert roof["bottleneck"] == "compute"
    np.testing.assert_allclose(roof["compute_s"], 1.0, rtol=1e-6)
    np.testing.assert_allclose(roof["memory_s"], 1e9 / 819e9, rtol=1e-6)
    np.testing.assert_allclose(roof["collective_s"], 1e9 / 50e9, rtol=1e-6)
    assert roof["roofline_fraction"] == pytest.approx(1.0)


def test_d2d_serve_decode_term():
    """KV-head-sharded decode: the d2d floor is the attention-output
    all-gather plus sampled ids, scaled by (N-1)/N; 1-way shards are free;
    analyze_costs only grows a fourth term when the bytes are passed."""
    from repro.configs import get_arch, reduced
    from repro.core.memfloor import d2d_bytes_serve_decode

    cfg = reduced(get_arch("qwen3-0.6b"))
    assert d2d_bytes_serve_decode(cfg, 8, 1)["total"] == 0.0

    d4 = d2d_bytes_serve_decode(cfg, 8, 4)
    n_attn = sum(1 for sp in cfg.all_layers()
                 if sp.mixer in ("full", "local"))
    want = 8 * cfg.n_heads * cfg.resolved_head_dim * 2 * n_attn * 0.75
    assert d4["attn_out_allgather"] == pytest.approx(want)
    assert d4["sampled_ids"] == pytest.approx(8 * 4 * 0.75)
    assert d4["total"] == pytest.approx(want + 8 * 4 * 0.75)
    # more shards move more bytes per device ((N-1)/N grows), never fewer
    d8 = d2d_bytes_serve_decode(cfg, 8, 8)
    assert d8["total"] > d4["total"]

    base = dict(flops_per_dev=1e12, bytes_per_dev=1e9,
                collective_bytes_per_dev=0.0, collectives={},
                arch="qwen3-0.6b", shape="decode_32k", n_chips=4)
    r = analyze_costs(**base)
    assert "d2d_s" not in r["roofline"]
    r2 = analyze_costs(**base, d2d_bytes_per_dev=d4["total"])
    assert r2["roofline"]["d2d_s"] == pytest.approx(
        d4["total"] / CHIP.ici_link_bw)
    # a d2d-dominated step flips the bottleneck
    r3 = analyze_costs(**base, d2d_bytes_per_dev=1e12)
    assert r3["roofline"]["bottleneck"] == "d2d"


def test_model_flops_formulas():
    """6·N·D for training; gemma2 train_4k ≈ 6 × 27.2e9 × 1.05e6 tokens."""
    mf = model_flops("gemma2-27b", "train_4k")
    tokens = 256 * 4096
    assert 0.8 * 6 * 27e9 * tokens < mf < 1.3 * 6 * 29e9 * tokens
    # decode: one token per sequence
    mf_dec = model_flops("gemma2-27b", "decode_32k")
    assert mf_dec == pytest.approx(mf / tokens * 128 / 3.0, rel=0.01)


def test_moe_uses_active_params():
    """deepseek-moe 16B total / ~3B active: train flops reflect active only."""
    from repro.configs import get_arch
    pc = get_arch("deepseek-moe-16b").param_count()
    assert pc["total"] / pc["active"] > 4.0
    mf = model_flops("deepseek-moe-16b", "train_4k")
    assert mf < 6 * 0.35 * pc["total"] * 256 * 4096


def test_dtype_peaks():
    """The published v5e peaks: bf16 and int8 only; halving precision
    doubles the MXU peak, and there is no fp8 MXU to borrow a peak for."""
    assert dtype_peak_flops("bfloat16") == CHIP.peak_bf16_flops == 197e12
    assert dtype_peak_flops("int8") == 393e12
    for dtype in ("float32", "float8_e4m3fn"):
        with pytest.raises(KeyError, match="no published peak"):
            dtype_peak_flops(dtype)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite"])
def test_peak_table_unknown_device_raises(kind):
    with pytest.raises(KeyError, match="no peak table entry"):
        chip_spec(kind)
    with pytest.raises(KeyError):
        dtype_peak_flops("bfloat16", kind)


def test_peak_table_keyed_by_device_kind():
    spec = chip_spec("TPU v5 lite")
    assert spec is CHIP
    assert spec.peak("bfloat16") == 197e12 and spec.hbm_bw == 819e9


def test_roofline_time_formulas():
    t = roofline_time(flops=197e12 * 256, bytes_hbm=819e9 * 256,
                      bytes_collective=50e9 * 256, n_chips=256)
    for v in t.values():
        np.testing.assert_allclose(v, 1.0, rtol=1e-6)


def test_dryrun_artifacts_consistent():
    """If the sweep has produced artifacts, sanity-check them."""
    import json
    from pathlib import Path
    d = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    files = sorted(d.glob("*__16x16__*.json")) if d.exists() else []
    if not files:
        pytest.skip("no dry-run artifacts yet")
    for f in files:
        r = json.loads(f.read_text())
        if r.get("status") == "skipped":
            continue
        assert r["status"] == "ok", f"{f.name}: {r.get('error')}"
        assert r["n_chips"] == 256
        if "roofline" in r:
            roof = r["roofline"]
            assert roof["bottleneck"] in ("compute", "memory", "collective")
            assert 0 <= roof["roofline_fraction"] <= 1.0 + 1e-9
