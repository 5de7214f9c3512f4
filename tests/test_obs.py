"""Observability subsystem unit tests: metrics registry, tracer, reports.

These are pure-Python tests (no model, no jit) — the counters-vs-engine
ground-truth checks live in tests/test_serve.py and the property oracle in
tests/test_allocator_props.py; here we pin the *contracts* of the obs
package itself: instrument semantics, Snapshot algebra and JSON round-trip,
Prometheus text shape, Chrome-trace structure and its validator's failure
modes, the StatsView dict compatibility layer, and the zero-division-safe
paths of the utilization report.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, MetricsRegistry, NullTracer, Snapshot,
                       Tracer, decode_utilization, validate_chrome_trace,
                       windows_from_trace, write_metrics_json)


# ---------------------------------------------------------------- metrics --

def test_counter_monotone_and_labeled():
    reg = MetricsRegistry()
    c = reg.counter("reqs", "requests", labels=("reason",))
    c.inc(reason="ok")
    c.inc(2, reason="ok")
    c.inc(reason="err")
    assert c.series() == {"reqs{reason=err}": 1.0, "reqs{reason=ok}": 3.0}
    with pytest.raises(ValueError):
        c.inc(-1, reason="ok")
    with pytest.raises(ValueError):
        c.inc()  # labeled counter requires its labels
    # unlabeled counter: value property + numpy-scalar coercion
    u = reg.counter("toks")
    u.inc(np.int64(5))
    assert u.value == 5.0 and type(u.value) is float


def test_gauge_set_and_inc():
    reg = MetricsRegistry()
    g = reg.gauge("live")
    g.set(4)
    g.inc(-1)
    assert g.value == 3.0
    g.set(np.float32(2.5))
    assert g.value == 2.5


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    [s] = h.series().values()
    assert s["count"] == 4 and s["sum"] == pytest.approx(55.55)
    # buckets are cumulative: le=0.1 holds 1, le=1 holds 2, le=10 holds 3
    assert s["buckets"] == {"0.1": 1, "1.0": 2, "10.0": 3}


def test_registry_get_or_create_shares_and_type_checks():
    reg = MetricsRegistry()
    a = reg.counter("prefix_evictions")
    b = reg.counter("prefix_evictions")
    assert a is b
    with pytest.raises(TypeError):
        reg.gauge("prefix_evictions")
    with pytest.raises(ValueError):
        reg.counter("prefix_evictions", labels=("who",))


def test_snapshot_delta_and_lookup():
    reg = MetricsRegistry()
    c = reg.counter("steps")
    g = reg.gauge("live")
    h = reg.histogram("win", buckets=(1.0,))
    c.inc(3)
    g.set(2)
    h.observe(0.5)
    snap0 = reg.snapshot()
    c.inc(4)
    g.set(7)
    h.observe(0.25)
    h.observe(3.0)
    d = reg.snapshot().delta(snap0)
    assert d["steps"] == 4.0          # counters subtract
    assert d["live"] == 7.0           # gauges take the later value
    assert d["win"]["count"] == 2 and d["win"]["sum"] == pytest.approx(3.25)
    assert d["win"]["buckets"]["1.0"] == 1
    assert "steps" in d and d.get("nope", "x") == "x"
    with pytest.raises(KeyError):
        d["nope"]


def test_snapshot_json_round_trip():
    reg = MetricsRegistry()
    reg.counter("a", labels=("k",)).inc(2, k="v")
    reg.gauge("b").set(1.5)
    reg.histogram("c", buckets=(0.5, 2.0)).observe(1.0)
    snap = reg.snapshot()
    back = Snapshot.from_json(snap.to_json())
    assert back == snap
    with pytest.raises(ValueError):
        Snapshot.from_json(json.dumps({"schema": "bogus"}))


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("reqs", "served requests").inc(3)
    reg.gauge("live").set(2)
    reg.histogram("lat", buckets=(0.5, 1.0)).observe(0.25)
    text = reg.to_prometheus()
    assert "# HELP repro_reqs_total served requests" in text
    assert "# TYPE repro_reqs_total counter" in text
    assert "repro_reqs_total 3" in text
    assert "repro_live 2" in text
    assert 'repro_lat_bucket{le="0.5"} 1' in text
    assert 'repro_lat_bucket{le="+Inf"} 1' in text
    assert "repro_lat_count 1" in text


def test_stats_view_dict_compat():
    reg = MetricsRegistry()
    reg.counter("prefills")
    reg.gauge("n_live")
    reg.histogram("hidden")          # histograms never appear in the view
    stats = reg.view()
    stats["prefills"] += 1
    stats["prefills"] += 1
    stats["n_live"] = 3
    stats["n_live"] -= 1
    assert stats["prefills"] == 2 and isinstance(stats["prefills"], int)
    assert dict(stats) == {"prefills": 2, "n_live": 2}
    assert "hidden" not in stats
    # counters refuse to move backwards even through the view
    with pytest.raises(ValueError):
        stats["prefills"] = 0
    with pytest.raises(TypeError):
        del stats["prefills"]


def test_stats_view_aliases():
    reg = MetricsRegistry()
    reg.counter("sched_skips")
    aliased = reg.view(aliases={"skips": "sched_skips"})
    aliased["skips"] += 5
    assert aliased["skips"] == 5
    assert dict(aliased) == {"skips": 5}
    assert reg.counter("sched_skips").value == 5.0
    with pytest.raises(KeyError):
        aliased["sched_skips"]       # closed view exposes alias keys only


# ----------------------------------------------------------------- tracer --

def test_tracer_spans_and_chrome_export():
    t = Tracer(buffer=64, clock=iter(range(100)).__next__)
    t.event("submit", uid=7)
    t.begin("request", uid=7)
    t.begin("prefill", uid=7, slot=np.int64(2), chunk=np.int64(16))
    t.end("prefill", uid=7, slot=2)
    t.end("request", uid=7)
    doc = t.to_chrome()
    summary = validate_chrome_trace(doc)
    assert summary == {"events": len(doc["traceEvents"]), "spans": 2,
                       "instants": 1, "requests": 1, "dropped": 0}
    # numpy scalars were coerced to JSON-safe types
    json.dumps(doc)
    b = next(e for e in doc["traceEvents"]
             if e["name"] == "prefill" and e["ph"] == "b")
    assert b["tid"] == 2 and b["args"] == {"chunk": 16, "uid": 7}


def test_tracer_close_open_keeps_named_spans():
    t = Tracer(buffer=64)
    t.begin("request", uid=1)
    t.begin("decode", uid=1, slot=0)
    t.close_open(1, keep=("request",), reason="preempted")
    assert t.open_spans(1) == ("request",)
    t.close_open(1)
    assert t.open_spans(1) == ()
    validate_chrome_trace(t.to_chrome())


def test_tracer_ring_buffer_drops_oldest():
    t = Tracer(buffer=4)
    for i in range(10):
        t.event("tick", uid=i)
    assert len(t) == 4 and t.dropped == 6
    assert [dict(e.args) for e in t.events()] == [{}] * 4
    assert [e.uid for e in t.events()] == [6, 7, 8, 9]
    assert t.to_chrome()["otherData"]["dropped"] == 6


def test_null_tracer_is_inert():
    assert not NULL_TRACER and not NullTracer().enabled
    NULL_TRACER.event("x", uid=1)
    NULL_TRACER.begin("request", uid=1)
    NULL_TRACER.close_open(1)
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []
    assert validate_chrome_trace(NULL_TRACER.to_chrome())["events"] == 0


def test_validator_rejects_broken_traces():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_chrome_trace({"nope": 1})
    # orphan end: an 'e' with no matching open 'b'
    t = Tracer(buffer=8)
    t.end("request", uid=3)
    with pytest.raises(ValueError, match="orphan end"):
        validate_chrome_trace(t.to_chrome())
    # unclosed request span
    t = Tracer(buffer=8)
    t.begin("request", uid=3)
    with pytest.raises(ValueError, match="orphan begin"):
        validate_chrome_trace(t.to_chrome())
    # lifecycle events but no request span at all
    t = Tracer(buffer=8)
    t.begin("decode", uid=3)
    t.end("decode", uid=3)
    with pytest.raises(ValueError, match="without a closed 'request'"):
        validate_chrome_trace(t.to_chrome())


# ----------------------------------------------------------------- report --

def _cfg():
    from repro.configs import get_arch, reduced
    return reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256, dtype="float32")


V5E = "TPU v5 lite"


def test_decode_utilization_zero_window_is_safe():
    row = decode_utilization(_cfg().replace(dtype="bfloat16"), tokens=0,
                             steps=0, wall_s=0.0, batch_sum=0, kv_row_sum=0,
                             device_kind=V5E)
    assert row["mfu"] == 0.0 and row["hbm_util"] == 0.0
    assert row["d2d_util"] == 0.0 and row["tok_per_s"] == 0.0


@pytest.mark.parametrize("kind,dtype", [("cpu", "bfloat16"),
                                        (V5E, "float32"), (None, "bfloat16")])
def test_decode_utilization_not_measured_off_table(kind, dtype):
    """A device kind or dtype outside the peak table (this CPU process
    included) gets no MFU or bandwidth share, never host time over a TPU
    peak."""
    row = decode_utilization(_cfg().replace(dtype=dtype), tokens=64,
                             steps=16, wall_s=1e-3, batch_sum=64,
                             kv_row_sum=64 * 40, device_kind=kind)
    for key in ("mfu", "hbm_util", "d2d_util"):
        assert row[key] == "not measured"
    assert row["tok_per_s"] == pytest.approx(64000.0)


def test_decode_utilization_measured_window():
    cfg = _cfg().replace(dtype="bfloat16")
    # a fast window: the tiny config's MFU must survive 6-decimal rounding
    row = decode_utilization(cfg, tokens=64, steps=16, wall_s=1e-3,
                             batch_sum=64, kv_row_sum=64 * 40, kv_shard=2,
                             device_kind=V5E)
    pc = cfg.param_count()
    per_tok = 2.0 * (pc["nonembed_active"] + pc["embedding"])
    assert row["flops_per_token"] == per_tok
    assert row["tok_per_s"] == pytest.approx(64000.0)
    assert row["avg_batch"] == pytest.approx(4.0)
    assert row["avg_context"] == pytest.approx(40.0)
    assert 0 < row["mfu"] < 1 and 0 < row["hbm_util"]
    assert row["d2d_util"] > 0 and row["devices"] == 2
    # single-device run moves no D2D traffic
    solo = decode_utilization(cfg, tokens=64, steps=16, wall_s=1e-3,
                              batch_sum=64, kv_row_sum=64 * 40, kv_shard=1,
                              device_kind=V5E)
    assert solo["d2d_util"] == 0.0


def test_windows_from_trace():
    t = Tracer(buffer=256, clock=iter(np.arange(0, 10, 0.01)).__next__)
    for _ in range(8):
        t.event("dispatch", n=2, kv=24)
        t.event("sync", n=2, tokens=2)
    rows = windows_from_trace(t, _cfg(), window_steps=4)
    assert len(rows) == 2
    assert rows[0]["steps"] == 4 and rows[0]["tokens"] == 8
    assert rows[0]["avg_batch"] == pytest.approx(2.0)
    assert windows_from_trace(NULL_TRACER, _cfg()) == []


def test_write_metrics_json_schema(tmp_path):
    reg = MetricsRegistry()
    reg.counter("decode_steps").inc(4)
    path = tmp_path / "m.json"
    payload = write_metrics_json(
        str(path), suite="unit", snapshot=reg.snapshot(),
        utilization={"mfu": 0.1}, extra={"note": "x"})
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert on_disk["schema"] == "repro-metrics-report-v1"
    assert on_disk["suite"] == "unit" and on_disk["extra"] == {"note": "x"}
    assert Snapshot.from_json(
        json.dumps(on_disk["snapshot"])) == reg.snapshot()
