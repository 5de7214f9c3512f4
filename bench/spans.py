"""The program's step spans in a profiler trace, for the readers of the
engine's host loop.

``ServeEngine.step`` marks its phases with ``serve.*`` spans
(``repro.obs.trace.STEP_SPANS``): ``serve.step`` holding ``serve.admit``,
``serve.prefill``, ``serve.decode.dispatch``, ``serve.decode.sync``,
``serve.decode.emit``, ``serve.account`` and ``serve.spec``. They land on
the profiler's clock beside the device ops, as the benchmark's ``bench.*``
spans do. :func:`tracing.load` keeps only the latter, so :func:`load` reads
the host events of both prefixes (:data:`HOST_PREFIXES`) and
:func:`of_run` finds a run's ``serve.*`` spans for a reader. A program
without these spans gives none, and its readers read nothing.

    python3 bench/spans.py <trace dir> <out.json.gz> [steps]

reduces the newest ``.xplane.pb`` under the directory as
``bench/tracing.py`` does, keeping the ``serve.*`` spans too; prints the
window's device idle time and its longest gaps by innermost host span, and
the phases that lie outside every ``serve.step`` (:func:`report`); and
writes the reduced trace as gzipped JSON, cut to ``steps`` decode steps
around the window's first prefill chunk when given (:func:`cut`).
"""
from __future__ import annotations

import bisect
import functools
import gzip
import json
import sys
from pathlib import Path

if __name__ == "__main__":      # run as a script: make ``bench`` importable
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import tracing  # noqa: E402

HOST_PREFIXES = ("bench.", "serve.")
SERVE = "serve."
#: where ``bench/run.py`` has the profiler write a traced run's window
TRACES = Path(__file__).resolve().parents[1] / "bench" / ".out" / "trace"


def host_events(path: Path, prefixes=HOST_PREFIXES) -> list:
    """``[name, start_ns, duration_ns]`` of the host events of ``path``
    whose names start with one of ``prefixes``, outer spans first where two
    start together."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                           for e in line.events
                           if e.name.startswith(prefixes))
    out.sort(key=lambda e: (e[1], -e[2]))
    return out


def load(path: Path) -> tracing.Trace:
    """:func:`tracing.load`, with the ``serve.*`` spans among the host
    events."""
    t = tracing.load(path)
    t.host = host_events(path)
    return t


@functools.lru_cache(maxsize=1)
def _host_events_at(path: str, mtime_ns: int) -> tuple:
    """:func:`host_events` of one version of a file, read once for all the
    readers of a run."""
    return tuple(host_events(Path(path)))


def of_run(ctx) -> list:
    """The ``serve.*`` spans of the traced run a reader's ``ctx`` holds.

    Taken from ``ctx.trace.host`` where it has them (a reduced trace);
    else from the newest ``.xplane.pb`` under :data:`TRACES`, which the run
    has just written, if its ``bench.window`` is the context's window.
    Empty where neither holds any."""
    own = [h for h in ctx.trace.host if h[0].startswith(SERVE)]
    if own:
        return own
    path = max(TRACES.rglob("*.xplane.pb"),
               key=lambda p: p.stat().st_mtime_ns, default=None)
    if path is None:
        return []
    host = _host_events_at(str(path), path.stat().st_mtime_ns)
    window = next((h for h in host if h[0] == "bench.window"), None)
    if window is None or (window[1], window[1] + window[2]) != (ctx.lo,
                                                               ctx.hi):
        return []
    return [h for h in host if h[0].startswith(SERVE)]


def named(spans: list, name: str, lo: int, hi: int) -> list:
    """The spans called ``name`` that start in [lo, hi)."""
    return [h for h in spans if h[0] == name and lo <= h[1] < hi]


def inside(children: list, parents: list) -> list[list]:
    """For each of ``parents``, the ``children`` that lie wholly inside
    it."""
    starts = sorted(c[1] for c in children)
    by_start = sorted(children, key=lambda c: c[1])
    out = []
    for _, s, d in parents:
        i = bisect.bisect_left(starts, s)
        j = bisect.bisect_left(starts, s + d)
        out.append([c for c in by_start[i:j] if c[1] + c[2] <= s + d])
    return out


def _busy_before(dev: tracing.Device, lo: int, hi: int):
    """A function of ``t``: the ns in [lo, t) in which a device op ran."""
    busy = tracing.union(dev.ops, lo, hi)
    starts = [s for s, _ in busy]
    cum = [0]
    for s, e in busy:
        cum.append(cum[-1] + e - s)

    def before(t: int) -> int:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0
        s, e = busy[i]
        return cum[i] + min(t, e) - s
    return before


def idle_inside(dev: tracing.Device, spans: list, lo: int, hi: int) -> int:
    """Nanoseconds in [lo, hi) that some interval of ``spans`` (``[name,
    start, duration]``) covers and in which no op ran on the device."""
    before = _busy_before(dev, lo, hi)
    return sum((e - s) - (before(e) - before(s))
               for s, e in tracing.union(spans, lo, hi))


def idle_by_span(host: list, dev: tracing.Device, lo: int, hi: int
                 ) -> dict[str, int]:
    """The device's idle ns in [lo, hi), each given to the innermost host
    span it falls in (``bench.window`` for time in no other span). The
    values sum to the window's idle time; host spans nest, as those of one
    thread do."""
    before = _busy_before(dev, lo, hi)

    def idle(s: int, e: int) -> int:
        s, e = max(s, lo), min(e, hi)
        return (e - s) - (before(e) - before(s)) if e > s else 0

    out = {"bench.window": idle(lo, hi)}
    stack: list = []
    for name, s, d in sorted((h for h in host if h[0] != "bench.window"),
                             key=lambda h: (h[1], -h[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        parent = stack[-1][0] if stack else "bench.window"
        n = idle(s, s + d)
        out[parent] -= n
        out[name] = out.get(name, 0) + n
        stack.append((name, s + d))
    return out


def longest_gaps(host: list, dev: tracing.Device, lo: int, hi: int,
                 n: int = 10) -> list:
    """The ``n`` longest stretches of [lo, hi) with no device op, as
    ``[seconds, innermost host span, its parent]`` (``bench.window`` for
    none), each named by the spans around its midpoint."""
    gaps, cur = [], lo
    for s, e in tracing.union(dev.ops, lo, hi) + [(hi, hi)]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        around = [h for h in host if h[1] <= mid < h[1] + h[2]
                  and h[0] != "bench.window"]
        around.sort(key=lambda h: (h[1], -h[2]))
        names = ["bench.window", "bench.window"] + [h[0] for h in around]
        out.append([(e - s) * 1e-9, names[-1], names[-2]])
    return out


def outside_steps(host: list, lo: int, hi: int) -> int:
    """How many ``serve.*`` phases that start in [lo, hi) lie in no
    ``serve.step``."""
    steps = [h for h in host if h[0] == "serve.step"]
    phases = [h for h in host if h[0].startswith(SERVE)
              and h[0] != "serve.step" and lo <= h[1] < hi]
    held = sum(len(c) for c in inside(phases, steps))
    return len(phases) - held


def report(t: tracing.Trace) -> str:
    """The window's device idle time by innermost host span, each with its
    share of the window, its longest gaps, and the ``serve.*`` phases
    outside every step."""
    lo, hi = t.span("bench.window")
    dev = t.devices[t.device_names()[0]]
    idle = idle_by_span(t.host, dev, lo, hi)
    out = [f"window {(hi - lo) * 1e-9:.6f} s, device idle "
           f"{100.0 * sum(idle.values()) / (hi - lo):.4f}%"]
    for name, n in sorted(idle.items(), key=lambda kv: -kv[1]):
        out.append(f"idle in {name}: {n * 1e-9:.6f} s, "
                   f"{100.0 * n / (hi - lo):.4f}% of the window")
    for sec, name, parent in longest_gaps(t.host, dev, lo, hi):
        out.append(f"gap {sec * 1e3:.3f} ms in {name} (in {parent})")
    out.append(f"serve.* phases outside a serve.step: "
               f"{outside_steps(t.host, lo, hi)}")
    return "\n".join(out)


def cut(t: tracing.Trace, steps: int) -> tracing.Trace:
    """The window cut to ``steps`` executions of the decode step, from the
    last one that starts before the window's first prefill chunk (from the
    window's first when no chunk runs in it), as the new ``bench.window``."""
    lo, hi = t.span("bench.window")
    dev = t.devices[t.device_names()[0]]
    decode = sorted(e[1] for e in dev.modules
                    if lo <= e[1] < hi and "_decode_all" in e[0])
    chunk = min((e[1] for e in dev.modules
                 if lo <= e[1] < hi and "_chunk_step" in e[0]), default=hi)
    first = max(bisect.bisect_left(decode, chunk) - 1, 0)
    if first < len(decode):
        lo = decode[first]
    if first + steps < len(decode):
        hi = decode[first + steps]
    c = t.trimmed(lo, hi)
    c.host = [h for h in c.host if h[0] != "bench.window"]
    c.host.append(["bench.window", lo, hi - lo])
    c.host.sort(key=lambda e: (e[1], -e[2]))
    return c


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    t = load(tracing.find_xplane(Path(args[0])))
    print(report(t))
    if len(args) > 2:
        t = cut(t, int(args[2]))
    with gzip.open(args[1], "wt") as f:
        json.dump(t.to_json(), f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
