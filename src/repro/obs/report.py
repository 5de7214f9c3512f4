"""Measured-utilization reporting: the software analogue of Occamy's
counter-derived utilization plots.

The engine's metrics registry accumulates *measured* decode windows —
``decode_window_s`` (histogram: wall seconds per engine step that dispatched
decode work), ``decode_window_tokens`` / ``decode_window_batch`` /
``decode_window_kv_rows`` (counters) — and this module joins them against
the analytic cost model in ``core/roofline.py`` / ``core/memfloor.py``:

* **MFU** — achieved model FLOP/s over the device pool's peak, with decode
  FLOPs/token = ``2 * (nonembed_active + embedding)`` params, exactly the
  convention ``roofline.model_flops`` uses for decode shapes.
* **HBM bandwidth utilization** — the per-step decode *floor* bytes from
  ``memfloor.hbm_bytes_floor`` (weights replicated in serve mode, KV cache
  sharded ``kv_shard``-way) replayed at the measured step rate, over
  the chip's ``hbm_bw``. This is a lower bound on true traffic, so the reported
  fraction is "what the floor model says we must have moved".
* **D2D bandwidth utilization** — ``memfloor.d2d_bytes_serve_decode`` at the
  measured average batch, over the chip's ``ici_link_bw`` (zero
  off-shard).

The three shares are device metrics: they are reported only when the run's
device kind (``jax.devices()[0].device_kind``) and the config's compute dtype
are in the peak table (``core/topology.py:PEAKS``). Anywhere else, a CPU run
included, they read ``"not measured"`` rather than dividing host time by a
TPU peak.

``utilization_report(engine)`` reads one engine; ``windows_from_trace``
re-derives a per-window series from a :class:`~repro.obs.trace.Tracer`'s
dispatch/sync instants when tracing was enabled.
"""
from __future__ import annotations

from typing import Any

__all__ = ["NOT_MEASURED", "decode_utilization", "utilization_report",
           "windows_from_trace", "write_metrics_json"]

NOT_MEASURED = "not measured"


def _chip(device_kind: str | None, dtype: str):
    """(chip spec, peak op/s) for a device kind in the peak table, or None."""
    from repro.core.topology import PEAKS

    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    spec = PEAKS.get(device_kind)
    if spec is None or str(dtype) not in dict(spec.peak_ops):
        return None
    return spec, spec.peak(dtype)


def _serve_decode_floor(cfg, *, batch: float, context: float,
                        kv_shard: int = 1) -> dict:
    """Per-device HBM floor bytes for ONE serve-mode decode step.

    Serve mode (``Partitioner(mode="serve")``) replicates weights and
    activations on every device and shards only the paged KV pools by KV
    head — so the floor joins the *replicated* weight/activation/logit
    terms with the *sharded* cache term, rather than taking either pure
    tensor-parallel view of ``hbm_bytes_floor``.
    """
    from repro.configs.base import ShapeConfig
    from repro.core.memfloor import MeshSizes, hbm_bytes_floor

    shape = ShapeConfig(name="obs-decode", kind="decode",
                        seq_len=max(int(round(context)), 1),
                        global_batch=max(batch, 1.0))
    full = hbm_bytes_floor(cfg, shape, MeshSizes(n_data=1, n_model=1),
                           dp=1, tp=1)
    if kv_shard <= 1:
        return full
    shard = hbm_bytes_floor(
        cfg, shape, MeshSizes(n_data=1, n_model=kv_shard),
        dp=1, tp=kv_shard)
    out = {"weights": full["weights"], "cache": shard["cache"],
           "activations": full["activations"], "logits": full["logits"]}
    out["total"] = sum(out.values())
    return out


def decode_utilization(cfg, *, tokens: float, steps: float, wall_s: float,
                       batch_sum: float, kv_row_sum: float,
                       kv_shard: int = 1,
                       device_kind: str | None = None) -> dict:
    """Join one measured decode window against the analytic model.

    ``tokens``: tokens committed in the window (spec-decode commits count);
    ``steps``: decode dispatches; ``wall_s``: measured wall seconds;
    ``batch_sum``: sum over dispatches of active decode slots;
    ``kv_row_sum``: sum over dispatches of context rows attended;
    ``device_kind``: the device that ran the window (default: this
    process's first device). MFU and the bandwidth shares need it in the
    peak table, else they read ``"not measured"``.
    """
    from repro.core.memfloor import d2d_bytes_serve_decode

    n_dev = max(int(kv_shard), 1)
    chip = _chip(device_kind, cfg.dtype)
    if steps <= 0 or wall_s <= 0:
        share = 0.0 if chip else NOT_MEASURED
        return {"tokens": int(tokens), "steps": int(steps),
                "wall_s": wall_s, "tok_per_s": 0.0, "avg_batch": 0.0,
                "avg_context": 0.0, "flops_per_token": 0.0,
                "achieved_tflops": 0.0, "mfu": share,
                "hbm_floor_bytes_per_step_dev": 0.0, "hbm_util": share,
                "d2d_bytes_per_step_dev": 0.0, "d2d_util": share,
                "devices": n_dev}

    avg_batch = batch_sum / steps
    avg_context = kv_row_sum / max(batch_sum, 1.0)

    pc = cfg.param_count()
    flops_per_token = 2.0 * (pc["nonembed_active"] + pc["embedding"])
    achieved = flops_per_token * tokens / wall_s

    floor = _serve_decode_floor(cfg, batch=avg_batch, context=avg_context,
                                kv_shard=n_dev)
    hbm_rate = floor["total"] * steps / wall_s          # per-device B/s
    d2d = d2d_bytes_serve_decode(cfg, max(int(round(avg_batch)), 1), n_dev)
    d2d_rate = d2d["total"] * steps / wall_s

    if chip:
        spec, peak = chip
        mfu = round(achieved / (peak * n_dev), 6)
        hbm_util = round(hbm_rate / spec.hbm_bw, 6)
        d2d_util = round(d2d_rate / spec.ici_link_bw, 6)
    else:
        mfu = hbm_util = d2d_util = NOT_MEASURED
    return {
        "tokens": int(tokens),
        "steps": int(steps),
        "wall_s": round(wall_s, 6),
        "tok_per_s": round(tokens / wall_s, 2),
        "avg_batch": round(avg_batch, 3),
        "avg_context": round(avg_context, 2),
        "flops_per_token": flops_per_token,
        "achieved_tflops": round(achieved / 1e12, 6),
        "mfu": mfu,
        "hbm_floor_bytes_per_step_dev": round(floor["total"], 1),
        "hbm_util": hbm_util,
        "d2d_bytes_per_step_dev": round(d2d["total"], 1),
        "d2d_util": d2d_util,
        "devices": n_dev,
    }


def utilization_report(engine) -> dict:
    """Aggregate measured-window utilization for one engine run."""
    snap = engine.metrics.snapshot()
    win = snap.histograms.get("decode_window_s",
                              {"count": 0, "sum": 0.0, "buckets": {}})
    return decode_utilization(
        engine.cfg,
        tokens=snap.counters.get("decode_window_tokens", 0.0),
        steps=win["count"],
        wall_s=win["sum"],
        batch_sum=snap.counters.get("decode_window_batch", 0.0),
        kv_row_sum=snap.counters.get("decode_window_kv_rows", 0.0),
        kv_shard=getattr(engine, "_kv_shard", 1),
    )


def windows_from_trace(trace, cfg, *, kv_shard: int = 1,
                       window_steps: int = 32) -> list[dict]:
    """Per-window utilization series from a tracer's decode instants.

    Groups consecutive ``dispatch`` events (which carry ``n`` active slots
    and ``kv`` context rows) into windows of ``window_steps`` dispatches;
    tokens come from the ``sync`` / ``spec_commit`` instants that land
    inside the window's time range. Requires tracing to have been enabled
    for the run — returns ``[]`` on an empty or disabled trace.
    """
    evs = trace.events()
    dispatches = [e for e in evs if e.name == "dispatch"]
    if not dispatches:
        return []
    emits = [(e.ts, dict(e.args)) for e in evs
             if e.name in ("sync", "spec_commit")]
    out = []
    for w0 in range(0, len(dispatches), window_steps):
        group = dispatches[w0:w0 + window_steps]
        t_lo = group[0].ts
        t_hi = (dispatches[w0 + window_steps].ts
                if w0 + window_steps < len(dispatches)
                else max(e.ts for e in evs))
        args = [dict(e.args) for e in group]
        tokens = sum(a.get("tokens", a.get("accepted", 0))
                     for ts, a in emits if t_lo <= ts <= t_hi)
        row = decode_utilization(
            cfg,
            tokens=tokens,
            steps=len(group),
            wall_s=max(t_hi - t_lo, 1e-9),
            batch_sum=sum(a.get("n", 0) for a in args),
            kv_row_sum=sum(a.get("kv", 0) for a in args),
            kv_shard=kv_shard)
        row["window"] = w0 // window_steps
        out.append(row)
    return out


def write_metrics_json(path: str, *, suite: str, snapshot,
                       utilization: dict | None = None,
                       extra: dict | None = None) -> dict:
    """The one metrics-JSON schema every benchmark and the launcher emit."""
    import json

    payload: dict[str, Any] = {
        "schema": "repro-metrics-report-v1",
        "suite": suite,
        "snapshot": snapshot.as_dict(),
    }
    if utilization is not None:
        payload["utilization"] = utilization
    if extra:
        payload["extra"] = extra
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    return payload
