"""Observability subsystem unit tests: metrics registry, tracer, reports.

Most are pure-Python tests (no model, no jit) — the counters-vs-engine
ground-truth checks live in tests/test_serve.py and the property oracle in
tests/test_allocator_props.py; here we pin the *contracts* of the obs
package itself: instrument semantics, Snapshot algebra and JSON round-trip,
Prometheus text shape, Chrome-trace structure and its validator's failure
modes, the StatsView dict compatibility layer, and the zero-division-safe
paths of the utilization report. The last section drives a tiny paged
engine: its step spans as a ``jax.profiler`` capture records them, and the
queue-wait and KV-reservation counters against hand-computed values.
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, STEP_SPANS, MetricsRegistry, NullTracer,
                       Snapshot, Tracer, decode_utilization,
                       validate_chrome_trace, write_metrics_json)


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


# ------------------------------------------------------------ step spans --

def test_span_is_a_context_manager_without_profiler():
    """Both tracers hand out a usable span with no profiler running, and a
    span is no ring event: the ring stays the request-lifecycle record."""
    for t in (Tracer(buffer=8), NULL_TRACER, NullTracer()):
        with t.span("serve.step"):
            with t.span("serve.prefill", uid=3, n=8):
                pass
        assert len(t) == 0
    assert STEP_SPANS[0] == "serve.step" and len(set(STEP_SPANS)) == 8


@pytest.fixture(scope="module")
def tiny():
    import jax

    from repro.models import init
    cfg = _cfg()
    return cfg, init(jax.random.PRNGKey(0), cfg)


def _host_spans(root) -> list[tuple[str, int, int, dict]]:
    """(name, start_ns, end_ns, stats) of every ``serve.*`` host event in
    the newest capture under ``root``, in start order."""
    import jax

    [path] = sorted(root.rglob("*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns), dict(e.stats))
                       for e in line.events if e.name.startswith("serve."))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_step_spans_in_profiler_trace(tiny, tmp_path):
    """A few requests stepped under ``jax.profiler.trace``: every host span
    is a ``STEP_SPANS`` name, every phase lies inside a ``serve.step``, and
    there is one sync per decode dispatch and one prefill span per chunk."""
    import jax

    from repro.serve import Request, ServeEngine
    cfg, params = tiny
    rng = np.random.default_rng(5)
    engine = ServeEngine(cfg, params, max_slots=2, max_len=64, paged=True,
                         page_size=8, prefill_chunk=8)
    before = engine.metrics.snapshot()
    with jax.profiler.trace(str(tmp_path)):
        for uid, (n, new) in enumerate(((12, 4), (20, 3), (5, 5))):
            engine.submit(Request(uid=uid, prompt=rng.integers(
                0, 256, n).astype(np.int32), max_new_tokens=new))
        steps = 0
        while engine._busy():
            engine.step()
            steps += 1
    d = engine.metrics.snapshot().delta(before)
    spans = _host_spans(tmp_path)
    names = [s[0] for s in spans]
    assert set(names) == set(STEP_SPANS) - {"serve.spec"}
    outer = [s for s in spans if s[0] == "serve.step"]
    assert len(outer) == steps
    for name, lo, hi, _ in spans:
        if name != "serve.step":
            assert any(s[1] <= lo and hi <= s[2] for s in outer), name
    assert names.count("serve.decode.sync") == d["decode_steps"] > 0
    assert names.count("serve.decode.dispatch") == d["decode_steps"]
    assert names.count("serve.prefill") == d["prefill_chunks"] == 2 + 3 + 1
    assert names.count("serve.admit") == names.count("serve.account") == steps
    chunks = [s[3] for s in spans if s[0] == "serve.prefill"]
    assert sorted(c["n"] for c in chunks) == [4, 4, 5, 8, 8, 8]
    assert {c["uid"] for c in chunks} == {0, 1, 2}


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "dense"])
def test_queue_wait_and_kv_reserved_counters(tiny, paged):
    """Two requests through one slot: each admission adds its queue wait,
    and every decode dispatch reserves the slot's granted pages (a dense
    slot: ``max_len`` rows), never fewer rows than it attends."""
    from repro.serve import Request, ServeEngine
    cfg, params = tiny
    rng = np.random.default_rng(6)
    kw = (dict(paged=True, page_size=8, prefill_chunk=8) if paged
          else dict(paged=False))
    engine = ServeEngine(cfg, params, max_slots=1, max_len=64, **kw)
    a = Request(uid=0, prompt=rng.integers(0, 256, 12).astype(np.int32),
                max_new_tokens=4)            # 16 tokens: 2 pages of 8
    b = Request(uid=1, prompt=rng.integers(0, 256, 20).astype(np.int32),
                max_new_tokens=5)            # 25 tokens: 4 pages of 8
    before = engine.metrics.snapshot()
    engine.run([a, b])
    d = engine.metrics.snapshot().delta(before)
    ra, rb = engine.results[0], engine.results[1]
    assert d["prefills"] == 2
    # a waits from its submit to the first step's admission; b until a
    # has finished, after a's last token and before b's first
    wait = d["queue_wait_s"]
    assert ra.token_ts[-1] - rb.submit_s < wait
    assert wait < (ra.token_ts[0] - ra.submit_s) + (rb.token_ts[0]
                                                    - rb.submit_s)
    per_step = (2 * 8, 4 * 8) if paged else (64, 64)
    assert d["decode_window_kv_reserved_rows"] == (
        per_step[0] * ra.decode_steps + per_step[1] * rb.decode_steps)
    assert ra.decode_steps == 3 and rb.decode_steps == 4
    assert 0 < d["decode_window_kv_rows"] <= d[
        "decode_window_kv_reserved_rows"]


def test_queue_wait_restarts_at_requeue(tiny):
    """A preempted request's next wait runs from its requeue, not from its
    submit: the counter stays under the lifecycle ring's admit stamps less
    each admission's latest submit or requeue, and far under what a wait
    from the submit would read."""
    from repro.serve import Request, ServeEngine
    cfg, params = tiny
    rng = np.random.default_rng(64)
    victim = Request(uid=0, prompt=rng.integers(0, 256, 16).astype(np.int32),
                     max_new_tokens=4)
    hi = Request(uid=1, prompt=rng.integers(0, 256, 8).astype(np.int32),
                 max_new_tokens=3, priority=5)
    ring = Tracer()
    engine = ServeEngine(cfg, params, max_slots=1, max_len=64, paged=True,
                         page_size=8, prefill_chunk=4, prefix_cache=True,
                         preemption=True, tracer=ring)
    engine.submit(victim)
    engine.step()                        # admitted, first chunk only
    engine.submit(hi)
    while engine._busy():
        engine.step()
    res = engine.results[victim.uid]
    assert res.preempted == 1 and res.queued_s > res.submit_s
    enqueued = {victim.uid: [res.submit_s, res.queued_s],
                hi.uid: [engine.results[hi.uid].submit_s]}
    admits = [e for e in ring.events() if e.name == "admit"]
    assert [e.uid for e in admits] == [0, 1, 0]
    assert engine.stats["prefills"] == 3
    # each admit event is stamped just after its placement: a bound above
    bound = sum(e.ts - enqueued[e.uid].pop(0) for e in admits)
    wait = engine.stats["queue_wait_s"]
    assert 0 < wait <= bound
    # measured from the submit instead, the victim's second wait would add
    # everything before its requeue
    assert bound < wait + (res.queued_s - res.submit_s)
