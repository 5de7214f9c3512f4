"""The readers of the engine's step spans and of its queue and KV counters.

``data/trace_spans.json.gz`` is a trace recorded on a TPU v5e: the
``deepseek-7b-l15.long-decode`` cell's window cut by ``bench/spans.py`` to
three decode steps from the admission of a 2,042-token prompt, whose first
chunks prefill beside them, with the program's ``serve.*`` spans kept among
the host events. The expected numbers are computed here by plain
loops over its events, not with the helpers of ``bench/spans.py``; those
are checked on small synthetic traces, and ``of_run`` on a capture made on
the CPU.
"""
from __future__ import annotations

from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import costs, run, spans, tracing

DATA = Path(__file__).resolve().parent / "data" / "trace_spans.json.gz"
REPO = Path(__file__).resolve().parents[2]
CELL = "deepseek-7b-l15.long-decode"
NEW = ("step_host_ms", "sync_idle_ms", "queue_wait_ms", "kv_used_share")


def _dev(*ops) -> tracing.Device:
    return tracing.Device(ops=[["op", s, d] for s, d in ops])


# ---- helpers on synthetic traces ----------------------------------------
def test_idle_inside_synthetic():
    # busy [10, 30) and [50, 60); host spans [0, 20), [25, 55), [40, 45)
    dev = _dev((10, 15), (20, 10), (50, 10))
    host = [["a", 0, 20], ["b", 25, 30], ["c", 40, 5]]
    # [0, 20): idle 0-10 -> 10; [25, 55): idle 30-50 -> 20 (c inside it)
    assert spans.idle_inside(dev, host, 0, 100) == 30
    assert spans.idle_inside(dev, host[:1], 0, 100) == 10
    assert spans.idle_inside(dev, host[2:], 0, 100) == 5
    # clipped to the window
    assert spans.idle_inside(dev, host, 5, 52) == 5 + 20
    assert spans.idle_inside(dev, [], 0, 100) == 0
    assert spans.idle_inside(_dev(), host, 0, 100) == 50


def test_idle_by_span_gives_each_gap_to_the_innermost_span():
    dev = _dev((10, 20), (50, 10))              # idle: 0-10, 30-50, 60-100
    host = [["bench.window", 0, 100], ["bench.step", 5, 70],
            ["serve.step", 6, 60], ["serve.decode.sync", 32, 10]]
    got = spans.idle_by_span(host, dev, 0, 100)
    assert got == {"bench.window": 5 + 25,      # 0-5, 75-100
                   "bench.step": 1 + 9,         # 5-6, 66-75 (60-66 in step)
                   "serve.step": 4 + 2 + 8 + 6,  # 6-10, 30-32, 42-50, 60-66
                   "serve.decode.sync": 10}
    assert sum(got.values()) == 100 - tracing.busy_ns(dev, 0, 100)


def test_inside_and_outside_steps():
    steps = [["serve.step", 0, 10], ["serve.step", 20, 10]]
    kids = [["serve.admit", 1, 2], ["serve.decode.sync", 25, 5],
            ["serve.decode.sync", 28, 5], ["serve.prefill", 12, 2]]
    held = spans.inside(kids, steps)
    assert held == [[kids[0]], [kids[1]]]
    assert spans.outside_steps(steps + kids, 0, 100) == 2


def _cpu_capture(tmp_path):
    """A CPU profile with a ``bench.window`` around two ``serve.*`` spans;
    returns (the window in trace ns, the reduced trace of it)."""
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path / "cell")):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("serve.step"):
                with jax.profiler.TraceAnnotation("serve.decode.sync"):
                    jnp.ones(4).block_until_ready()
    path = tracing.find_xplane(tmp_path)
    host = spans.host_events(path)
    _, lo, d = next(h for h in host if h[0] == "bench.window")
    t = tracing.Trace(host=[h for h in host if h[0].startswith("bench.")])
    return lo, lo + d, t


def test_of_run_reads_the_runs_own_capture(tmp_path, monkeypatch):
    lo, hi, t = _cpu_capture(tmp_path)
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    got = spans.of_run(SimpleNamespace(trace=t, lo=lo, hi=hi))
    assert [h[0] for h in got] == ["serve.step", "serve.decode.sync"]
    assert lo <= got[0][1] and got[1][1] + got[1][2] <= hi
    # another run's window, or no capture at all: nothing to read
    assert spans.of_run(SimpleNamespace(trace=t, lo=lo + 1, hi=hi)) == []
    monkeypatch.setattr(spans, "TRACES", tmp_path / "none")
    assert spans.of_run(SimpleNamespace(trace=t, lo=lo, hi=hi)) == []


# ---- the readers on the recorded chip trace ------------------------------
@pytest.fixture(scope="module")
def chip():
    t = tracing.Trace.read_json(DATA)
    lo, hi = t.span("bench.window")
    return t, lo, hi


def _ctx(trace, lo, hi, counters=None):
    cell = run.load_cell(REPO, CELL)
    return run.Context(
        trace=trace, lo=lo, hi=hi,
        counters=counters if counters is not None else {
            "decode_steps": 8, "decode_window_batch": 24,
            "decode_window_kv_rows": 60_000, "prefills": 2,
            "queue_wait_s": 0.0007, "decode_window_kv_reserved_rows": 72_000},
        shape=cell.arch.shape(cell.config),
        peaks=costs.peaks("TPU v5 lite"),
        engine={"max_slots": 3, "max_len": 4096, "chunk": 256, "page": 16,
                "n_blocks": 975},
        requests=[{"prompt": 2042, "max_new": 700, "prefix": -1,
                   "tokens_ts": [1.0, 1.1]}],
        chips=1, extra={"t0": 0.5, "t1": 2.0, "prefix_tokens": 0})


def _read(name, ctx):
    return run.load_reader(REPO, name)(ctx)


def test_recorded_trace_holds_every_phase_inside_a_step(chip):
    t, lo, hi = chip
    names = {h[0] for h in t.host}
    assert {"serve.step", "serve.admit", "serve.prefill",
            "serve.decode.dispatch", "serve.decode.sync",
            "serve.decode.emit", "serve.account"} <= names
    assert spans.outside_steps(t.host, lo, hi) == 0
    steps = spans.named(t.host, "serve.step", lo, hi)
    assert len(steps) == 3
    # one of each phase per step; the last step's tail runs past the cut
    for phase, per_step in (
            ("serve.admit", [1, 1, 1]), ("serve.prefill", [1, 1, 1]),
            ("serve.decode.dispatch", [1, 1, 1]),
            ("serve.decode.sync", [1, 1, 1]),
            ("serve.decode.emit", [1, 1, 0]), ("serve.account", [1, 1, 0])):
        held = spans.inside(spans.named(t.host, phase, lo, hi), steps)
        assert [len(c) for c in held] == per_step, phase
    # the idle time by innermost span adds up to the window's
    dev = t.devices[t.device_names()[0]]
    idle = spans.idle_by_span(t.host, dev, lo, hi)
    assert sum(idle.values()) == hi - lo - tracing.busy_ns(dev, lo, hi)
    assert max(idle, key=idle.get) == "serve.decode.sync"


def test_step_host_ms_by_hand(chip):
    t, lo, hi = chip
    own, n = 0, 0
    for name, s, d in t.host:
        if name == "serve.step" and lo <= s < hi:
            n += 1
            own += d
            for name2, s2, d2 in t.host:
                if (name2 == "serve.decode.sync" and s <= s2
                        and s2 + d2 <= s + d):
                    own -= d2
    assert n > 0
    assert _read("step_host_ms", _ctx(t, lo, hi)) == pytest.approx(
        own / n * 1e-6, rel=1e-12)


def test_sync_idle_ms_by_hand(chip):
    t, lo, hi = chip
    dev = t.devices[t.device_names()[0]]
    idle, n = 0, 0
    for name, s, d in t.host:
        if name != "serve.decode.sync" or not lo <= s < hi:
            continue
        n += 1
        e = min(s + d, hi)          # the window's last wait runs past it
        idle += e - s - sum(be - bs
                            for bs, be in tracing.union(dev.ops, s, e))
    assert n > 0
    assert _read("sync_idle_ms", _ctx(t, lo, hi)) == pytest.approx(
        idle / n * 1e-6, rel=1e-12)


def test_counter_readers():
    ctx = SimpleNamespace(counters={
        "prefills": 2, "queue_wait_s": 0.0007,
        "decode_window_kv_rows": 60_000,
        "decode_window_kv_reserved_rows": 72_000})
    assert _read("queue_wait_ms", ctx) == pytest.approx(0.35)
    assert _read("kv_used_share", ctx) == pytest.approx(100 * 60 / 72)
    # a program without the counters: nothing to read
    none = SimpleNamespace(counters={"prefills": 2,
                                     "decode_window_kv_rows": 60_000})
    assert _read("queue_wait_ms", none) is None
    assert _read("kv_used_share", none) is None


def test_span_readers_read_nothing_without_spans(chip, tmp_path,
                                                 monkeypatch):
    """A program with no ``serve.*`` spans (as before they existed): the
    span readers return None and raise nothing."""
    t, lo, hi = chip
    bare = tracing.Trace(devices=t.devices,
                         host=[h for h in t.host
                               if not h[0].startswith("serve.")])
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    for name in ("step_host_ms", "sync_idle_ms"):
        assert _read(name, _ctx(bare, lo, hi)) is None


def test_existing_readers_and_breakdown_unchanged_by_serve_spans(chip):
    t, lo, hi = chip
    bare = tracing.Trace(devices=t.devices,
                         host=[h for h in t.host
                               if not h[0].startswith("serve.")])
    assert len(bare.host) < len(t.host)
    cell = run.load_cell(REPO, CELL)
    old = [m["name"] for m in cell.per_layer if m["name"] not in NEW]
    assert len(old) == 7
    read = [(_read(name, _ctx(t, lo, hi)), _read(name, _ctx(bare, lo, hi)))
            for name in old]
    assert all(a == b for a, b in read)
    assert sum(a is not None for a, _ in read) >= 5
    assert tracing.top_ops(t.devices[t.device_names()[0]], lo, hi) == \
        tracing.top_ops(bare.devices[bare.device_names()[0]], lo, hi)
    gaps = tracing.idle_gaps(t, t.devices[t.device_names()[0]], lo, hi)
    assert gaps == tracing.idle_gaps(
        bare, bare.devices[bare.device_names()[0]], lo, hi)
    assert all(name.startswith("bench.") for name, _ in gaps)
