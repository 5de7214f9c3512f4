"""Shared benchmark utilities: timing, CSV emit, multi-device subprocess."""
from __future__ import annotations

import os
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
OUTDIR = ROOT / "experiments" / "bench"

#: flattened per-run summary columns (benchmarks.run --metrics-dir)
METRICS_SUMMARY_COLS = (
    "suite", "tokens", "steps", "wall_s", "tok_per_s", "mfu", "hbm_util",
    "d2d_util", "decode_steps", "prefills", "prefix_hits", "preemptions",
    "spec_accepted", "blocks_granted", "blocks_released")


def metrics_path(suite: str) -> Path:
    """Where a suite's metrics-report JSON lands: ``REPRO_METRICS_DIR``
    (set by ``benchmarks.run --metrics-dir``) or experiments/bench/."""
    out = Path(os.environ.get("REPRO_METRICS_DIR") or OUTDIR)
    out.mkdir(parents=True, exist_ok=True)
    return out / f"{suite}.metrics.json"


def emit_metrics(suite: str, engine, extra: dict | None = None) -> dict:
    """Write a suite's engine metrics + utilization in the one shared
    schema (repro-metrics-report-v1) every serve benchmark and the
    launcher emit."""
    from repro.obs import utilization_report, write_metrics_json
    return write_metrics_json(str(metrics_path(suite)), suite=suite,
                              snapshot=engine.metrics.snapshot(),
                              utilization=utilization_report(engine),
                              extra=extra)


def summarize_metrics(payload: dict) -> dict:
    """One flat CSV row from a repro-metrics-report-v1 payload."""
    snap = payload.get("snapshot", {})
    c = snap.get("counters", {})
    u = payload.get("utilization", {})
    return {
        "suite": payload.get("suite", ""),
        "tokens": u.get("tokens", ""),
        "steps": u.get("steps", ""),
        "wall_s": u.get("wall_s", ""),
        "tok_per_s": u.get("tok_per_s", ""),
        "mfu": u.get("mfu", ""),
        "hbm_util": u.get("hbm_util", ""),
        "d2d_util": u.get("d2d_util", ""),
        "decode_steps": c.get("decode_steps", ""),
        "prefills": c.get("prefills", ""),
        "prefix_hits": c.get("prefix_hits", ""),
        "preemptions": c.get("preemptions", ""),
        "spec_accepted": c.get("spec_accepted", ""),
        "blocks_granted": c.get("blocks_granted", ""),
        "blocks_released": c.get("blocks_released", ""),
    }


def timeit(fn, *args, n: int = 3, warmup: int = 1, **kw) -> tuple:
    import jax
    out = None
    for _ in range(warmup):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / n


def emit(rows: list[dict], name: str) -> None:
    """Print rows as CSV and persist under experiments/bench/<name>.csv.

    The header is the ordered union of every row's keys (not just the
    first row's) — suites that append summary rows with disjoint keys
    used to render them as all-empty ",,,," lines. Rows whose rendered
    cells are all empty are dropped rather than written."""
    if not rows:
        print(f"[{name}] no rows")
        return
    cols: list[str] = []
    for r in rows:
        for c in r:
            if c not in cols:
                cols.append(c)
    lines = [",".join(cols)]
    for r in rows:
        cells = [str(r.get(c, "")) for c in cols]
        if not any(cells):
            continue
        lines.append(",".join(cells))
    text = "\n".join(lines)
    print(f"\n=== {name} ===")
    print(text)
    OUTDIR.mkdir(parents=True, exist_ok=True)
    (OUTDIR / f"{name}.csv").write_text(text + "\n")


def read_rows(name: str) -> list[dict]:
    """Read back an ``emit()``-style CSV as dicts, skipping blank/all-empty
    lines (tolerates trailing ",,,," rows from older emit versions)."""
    import csv
    path = OUTDIR / f"{name}.csv"
    if not path.exists():
        return []
    with path.open(newline="") as fh:
        return [r for r in csv.DictReader(fh)
                if any(v.strip() for v in r.values() if v is not None)]


def run_subprocess(code: str, n_devices: int = 8, timeout: int = 1200) -> str:
    """Run a snippet with N fake CPU devices; return stdout. The child is
    held to the CPU: an accelerator belongs to the parent process."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [SRC, str(ROOT), env.get("PYTHONPATH", "")])
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"bench subprocess failed:\n{proc.stderr[-3000:]}")
    return proc.stdout
