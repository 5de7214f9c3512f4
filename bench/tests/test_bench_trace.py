"""The trace reduction, checked on a trace recorded on a TPU v5e: two decode
steps and the twelve prefill chunks around them of the
``qwen3-0.6b.chat`` cell, reduced by ``bench/tracing.py`` and kept as
``data/trace_small.json.gz``. The expected numbers were read off that
trace by hand (a 1-microsecond occupancy timeline for the union)."""
from __future__ import annotations

from pathlib import Path

import numpy as np
import pytest

from bench import tracing

DATA = Path(__file__).resolve().parent / "data" / "trace_small.json.gz"


@pytest.fixture(scope="module")
def trace():
    t = tracing.Trace.read_json(DATA)
    lo, hi = t.span("bench.window")
    return t, t.devices[t.device_names()[0]], lo, hi


def test_busy_union_and_idle_share(trace):
    t, dev, lo, hi = trace
    busy = tracing.busy_ns(dev, lo, hi)
    assert busy == 2_076_708_207
    assert hi - lo == 2_105_551_322
    # an independent count: microseconds in which some op ran
    tl = np.zeros((hi - lo) // 1000 + 1, bool)
    for _, s, d in dev.ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            tl[(a - lo) // 1000:(b - lo + 999) // 1000] = True
    assert abs(int(tl.sum()) * 1000 - busy) < 200_000
    # nested ops (a layer loop and its body) are counted once
    assert busy < sum(d for _, s, d in dev.ops if lo <= s < hi)
    assert tracing.union([["a", 0, 10], ["b", 5, 10], ["c", 20, 5]],
                         0, 100) == [(0, 15), (20, 25)]


def test_program_time_per_execution(trace):
    _, dev, lo, hi = trace
    t, n = tracing.program_time(dev, r"_decode_all", lo, hi)
    assert n == 2 and t == pytest.approx(0.575703534, abs=1e-9)
    t, n = tracing.program_time(dev, r"_chunk_step", lo, hi)
    assert n == 12 and t == pytest.approx(1.501116829, abs=1e-9)


def test_kernel_sums_by_name(trace):
    _, dev, lo, hi = trace
    t, n = tracing.op_time(dev, "_pa_kernel", lo, hi)
    # one paged-attention call per layer per decode step
    assert n == 2 * 28 and t == pytest.approx(0.18160852, abs=1e-9)
    t, n = tracing.op_time(dev, "_gemm_kernel", lo, hi)
    # gate, up and down of every layer of every step
    assert n == (2 + 12) * 28 * 3 and t == pytest.approx(0.082619933,
                                                          abs=1e-9)
    assert tracing.kind("_pa_kernel.9") == "_pa_kernel"
    assert tracing._instruction(
        "%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") == \
        "fusion.12"


def test_breakdown(trace):
    t, dev, lo, hi = trace
    top = tracing.top_ops(dev, lo, hi)
    assert len(top) == 10 and "while" not in [k for k, _ in top]
    assert top[0][0] == "fusion"
    assert [k for k, _ in top].index("_pa_kernel") < 10
    gaps = tracing.idle_gaps(t, dev, lo, hi)
    assert len(gaps) == 10
    assert all(name.startswith("bench.") for name, _ in gaps)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert sum(s for _, s in gaps) <= (hi - lo - tracing.busy_ns(
        dev, lo, hi)) * 1e-9 + 1e-12
