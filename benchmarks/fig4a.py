"""Paper Fig. 4a — FPU utilization across regular→irregular workloads.

The paper's silicon result: dense GEMM 89%, stencil 83%, GCN 54%, SpMM 42% —
utilization declines monotonically with access irregularity, and the
streaming units (SUs) recover large factors over the non-streamed baseline.

This framework's analogue (CPU container; TPU is the target):
1. *achievable-utilization bound* per workload from the roofline model —
   util = compute_s / max(compute_s, memory_s) with each workload's FLOPs and
   HBM bytes at TPU v5e constants. The paper's monotone ordering must emerge.
2 *streaming speedup*: packed (index-sorted, 8-wide) gather vs naive
   per-row gather — the C5c mechanism's byte efficiency (paper: 4.8x,
   ideal 8x for the random pattern).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import emit, timeit
from repro.core.topology import CHIP
from repro.kernels import ops, ref, use_backend


def _util(flops: float, bytes_hbm: float, dtype="bfloat16") -> float:
    peak = CHIP.peak(dtype)
    t_c = flops / peak
    t_m = bytes_hbm / CHIP.hbm_bw
    return t_c / max(t_c, t_m)


def workloads(n: int = 4096, nnz_frac: float = 0.01) -> list[dict]:
    """FLOPs & minimum HBM bytes for the paper's four workloads (bf16),
    *with the paper's own data-movement optimizations applied*: temporal
    blocking keeps stencil tiles VMEM-resident across sweeps (paper cites
    [15]/[16]); the C5c temporal coalescer gives gathered rows cache reuse.

    Machine-balance caveat (DESIGN.md §2): Occamy's balance is ~1 FLOP/B
    (0.77 TF vs 0.82 TB/s) while v5e's is ~240 FLOP/B, so the *absolute*
    utilizations of irregular workloads compress on TPU; the paper anchor
    is the monotone regular->irregular ordering, which must survive.
    """
    rows = []
    # dense GEMM n^3: 2n^3 flops, 3n^2 tiles streamed once (C1 pipeline)
    rows.append({"workload": "GEMM", "flops": 2 * n**3,
                 "bytes": 3 * n * n * 2})
    # star-7 stencil, T=64 sweeps temporally blocked in VMEM: grid crosses
    # HBM once per block of sweeps instead of once per sweep
    T = 64
    rows.append({"workload": "STC", "flops": 13 * n * n * T,
                 "bytes": 2 * n * n * 2})
    # GCN layer (A X) W: deg-16 gather with coalescer reuse ~deg, then GEMM
    deg, f = 16, 256
    nnz = n * deg
    gcn_bytes = (nnz * 4                 # indices
                 + nnz * 2 * f // deg    # gathered rows, coalesced reuse
                 + n * f * 2 * 2         # X in, out
                 + f * f * 2)            # W
    rows.append({"workload": "GCN",
                 "flops": 2 * nnz * f + 2 * n * f * f,
                 "bytes": gcn_bytes})
    # SpMM: sparse A (1%) x dense B: gather-dominated, VMEM-limited reuse 8
    nnz2 = int(n * n * nnz_frac)
    rows.append({"workload": "SpMM", "flops": 2 * nnz2 * f,
                 "bytes": nnz2 * (4 + 4) + nnz2 * 2 * f // 8
                 + n * f * 2 * 2})
    for r in rows:
        r["ai_flop_per_byte"] = round(r["flops"] / r["bytes"], 2)
        r["util_bound"] = round(_util(r["flops"], r["bytes"]), 3)
    return rows


def streaming_speedup() -> list[dict]:
    """Packed irregular streams (C5c) vs naive narrow gathers.

    Byte-efficiency model (what the D2D/HBM links see): a naive narrow
    access moves a full 256-bit minimum HBM transaction per <=64-bit row
    element; packing 8 requests per wide flit + coalescing duplicate rows
    approaches the ideal 8x. We report the modeled efficiency for the random
    pattern (paper: 4.8x) AND the measured CPU wall-time of both kernel paths.
    """
    k = jax.random.PRNGKey(0)
    table = jax.random.normal(k, (65536, 32), jnp.float32)
    idx = jax.random.randint(k, (8192,), 0, 65536)

    with use_backend("interpret"):
        _, t_naive = timeit(ops.gather_rows, table, idx, n=2)
        _, t_packed = timeit(ops.packed_gather_rows, table, idx, pack=8, n=2)
        got = ops.packed_gather_rows(table, idx, pack=8)
    exact = bool((np.asarray(got) == np.asarray(table)[np.asarray(idx)]).all())

    # byte model: naive moves 32B (256-bit) per 8B useful row-chunk element;
    # packed coalesces sorted duplicates and fills 32B lines 8/8.
    elem_bytes = 8
    line = 32
    naive_wire = len(idx) * line
    uniq = len(np.unique(np.asarray(idx)))
    packed_wire = uniq * line / (line // elem_bytes) * (line // elem_bytes) / 8 + len(idx) * elem_bytes
    model_gain = naive_wire / packed_wire
    return [{
        "mechanism": "packed_gather(C5c)",
        "paper_speedup": 4.8, "ideal": 8.0,
        "modeled_byte_efficiency_gain": round(model_gain, 2),
        "cpu_interpret_speedup": round(t_naive / t_packed, 2),
        "exact": exact,
    }]


def main() -> list[dict]:
    rows = workloads()
    utils = [r["util_bound"] for r in rows]
    assert all(a >= b for a, b in zip(utils, utils[1:])), \
        f"utilization must decline with irregularity: {utils}"
    paper = {"GEMM": 0.89, "STC": 0.83, "GCN": 0.54, "SpMM": 0.42}
    for r in rows:
        r["paper_fpu_util"] = paper[r["workload"]]
    rows += streaming_speedup()
    emit(rows, "fig4a")
    return rows


if __name__ == "__main__":
    main()
