"""Paper Fig. 4b — GEMM throughput/efficiency vs precision with expanding
(widening) accumulation.

Paper: FP64→FP8 GEMM on Occamy scales ~2x per halving; expanding (widening)
accumulation costs ~nothing (even 6.5% *better* energy on FP16-EXP) thanks to
dedicated widening dot-product units.

TPU analogue: fp32 → bf16 → fp8 feeding the MXU with fp32 accumulation
(``preferred_element_type``), the MXU's native widening mode. We report:
  * the published peak per precision where v5e has one (bf16; its int8
    MXU peak is 2x bf16, and it publishes none for fp32 or fp8),
  * numerical error of widening vs same-precision accumulation (why
    expanding accumulation is the right default — the paper's C2 insight),
  * measured CPU wall-time ratios as a sanity signal only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._util import emit, timeit
from repro.core.topology import dtype_peak_flops
from repro.kernels import ref

N = 512


def _err(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / (np.abs(b).max() + 1e-12))


def main() -> list[dict]:
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    x64 = jax.random.normal(k1, (N, N), jnp.float32)
    w64 = jax.random.normal(k2, (N, N), jnp.float32)
    oracle = np.asarray(x64, np.float64) @ np.asarray(w64, np.float64)

    rows = []
    for dtype, name in [(jnp.float32, "fp32"), (jnp.bfloat16, "bf16"),
                        (jnp.float8_e4m3fn, "fp8_e4m3")]:
        x = x64.astype(dtype)
        w = w64.astype(dtype)
        # widening (expanding) accumulation — MXU-native
        wide = jnp.dot(x, w, preferred_element_type=jnp.float32)
        # non-expanding accumulation: same inputs, but the running
        # accumulator is held at narrow precision (bf16; fp8 accumulates in
        # bf16 — the paper's FP8 GEMM also expands only to FP16). Simulated
        # by chunked K with a downcast after every partial sum.
        acc_dtype = jnp.float32 if dtype == jnp.float32 else jnp.bfloat16
        chunk = 32
        narrow = jnp.zeros((N, N), acc_dtype)
        for i in range(0, N, chunk):
            part = jnp.dot(x[:, i:i + chunk], w[i:i + chunk, :],
                           preferred_element_type=jnp.float32)
            narrow = (narrow.astype(jnp.float32) + part).astype(acc_dtype)
        _, t = timeit(lambda: jnp.dot(x, w,
                                      preferred_element_type=jnp.float32),
                      n=3)
        # v5e publishes bf16 and int8 MXU peaks only: fp32 and fp8 have none
        peak = dtype_peak_flops("bfloat16") if name == "bf16" else None
        rows.append({
            "precision": name,
            "peak_tflops_per_chip": (round(peak / 1e12, 1) if peak
                                     else "not published"),
            "roofline_vs_bf16": (round(peak / dtype_peak_flops("bfloat16"), 2)
                                 if peak else "not published"),
            "err_widening_accum": round(_err(wide, oracle), 5),
            "err_narrow_accum": round(_err(narrow, oracle), 5),
            "cpu_ms": round(t * 1e3, 2),
        })

    # widening accumulation must be strictly more accurate than narrow
    # accumulation at every precision
    assert rows[1]["roofline_vs_bf16"] == 1.0
    for r in rows[1:]:
        assert r["err_widening_accum"] < r["err_narrow_accum"]
    emit(rows, "fig4b")
    return rows


if __name__ == "__main__":
    main()
