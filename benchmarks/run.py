"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig4a,fig7] [--skip-slow]
                                          [--dry-run]

Each module prints a CSV (also persisted to experiments/bench/<name>.csv)
and asserts its paper-anchor directional claims (DESIGN.md §8).

``--dry-run`` imports every suite, resolves the kernel-backend registry, and
exits without running — the CI smoke step that catches import/registration
breakage in seconds.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path


def _collect_metrics(metrics_dir: str) -> None:
    """Append one flat CSV row per suite metrics-report found in
    ``metrics_dir`` to experiments/bench/metrics_runs.csv (header written
    once; rows accumulate across harness runs)."""
    from benchmarks._util import METRICS_SUMMARY_COLS, OUTDIR, \
        summarize_metrics
    rows = []
    for p in sorted(Path(metrics_dir).glob("*.metrics.json")):
        try:
            payload = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError) as e:
            print(f"[metrics] skipping {p.name}: {e}")
            continue
        if payload.get("schema") != "repro-metrics-report-v1":
            print(f"[metrics] skipping {p.name}: wrong schema")
            continue
        rows.append(summarize_metrics(payload))
    if not rows:
        print(f"[metrics] no suite reports under {metrics_dir}")
        return
    out = OUTDIR / "metrics_runs.csv"
    OUTDIR.mkdir(parents=True, exist_ok=True)
    lines = [] if out.exists() else [",".join(METRICS_SUMMARY_COLS)]
    lines += [",".join(str(r.get(c, "")) for c in METRICS_SUMMARY_COLS)
              for r in rows]
    with open(out, "a") as f:
        f.write("\n".join(lines) + "\n")
    print(f"[metrics] appended {len(rows)} suite rows to {out}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip table1 (512-device compiles) unless cached")
    ap.add_argument("--dry-run", action="store_true",
                    help="import suites + registry and exit without running")
    ap.add_argument("--metrics-dir", default=None,
                    help="collect each suite's metrics-report JSON here and "
                         "append one summary CSV row per suite to "
                         "experiments/bench/metrics_runs.csv")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.metrics_dir:
        # suites resolve their report path through _util.metrics_path
        os.makedirs(args.metrics_dir, exist_ok=True)
        os.environ["REPRO_METRICS_DIR"] = args.metrics_dir

    from benchmarks import (fig4a, fig4b, fig4c, fig7, prefix_cache,
                            quant_accuracy, serve_latency, serve_throughput,
                            sparse_gemm, spec_decode, table1)
    suites = {"fig4a": fig4a.main, "fig4b": fig4b.main, "fig4c": fig4c.main,
              "fig7": fig7.main, "prefix": prefix_cache.main,
              "quant": quant_accuracy.main, "serve": serve_throughput.main,
              "latency": serve_latency.main, "sparse": sparse_gemm.main,
              "spec": spec_decode.main, "table1": table1.main}
    if args.only:
        keep = args.only.split(",")
        suites = {k: v for k, v in suites.items() if k in keep}

    if args.dry_run:
        from repro.kernels.dispatch import registry, resolve_backend
        print(f"suites: {', '.join(suites)}")
        print(f"kernel backend: {resolve_backend().name} "
              f"(platform {resolve_backend().platform})")
        print("registered ops:")
        for line in registry.describe().splitlines():
            print(f"  {line}")
        print("dry-run OK")
        return

    failures = []
    for name, fn in suites.items():
        t0 = time.time()
        try:
            fn()
            print(f"[{name}] OK ({time.time() - t0:.1f}s)\n")
        except Exception as e:
            failures.append(name)
            print(f"[{name}] FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()
    if args.metrics_dir:
        _collect_metrics(args.metrics_dir)
    if failures:
        sys.exit(f"benchmark failures: {failures}")
    print("all benchmarks passed")


if __name__ == "__main__":
    main()
