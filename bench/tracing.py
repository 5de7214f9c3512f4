"""Reduce a profiler trace to the numbers the per-layer metrics read.

:func:`load` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
plain :class:`Trace` (lists of ``[name, start_ns, duration_ns]``), which is
all the rest of this module and the metric readers see; a trimmed one is
kept as JSON for the tests. Device events come from the device planes'
``XLA Modules`` line (one event per execution of a compiled program, named
``jit_<function>(<hash>)``) and ``XLA Ops`` line (one per HLO instruction,
named by it: ``fusion.12``, ``while.5``, and a Pallas kernel by its kernel
function, ``_pa_kernel.3``); host events are the benchmark's own spans
(``bench.*``). The TPU trace carries no ``jax.named_scope`` on op events,
so a kernel is found by its Pallas function's name.
"""
from __future__ import annotations

import gzip
import json
import re
from dataclasses import dataclass, field
from pathlib import Path

MODULES, OPS = "XLA Modules", "XLA Ops"
#: ops that contain other ops of the same line: left out of op rankings
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Device:
    modules: list = field(default_factory=list)   # [name, start, dur]
    ops: list = field(default_factory=list)       # [name, start, dur]


@dataclass
class Trace:
    devices: dict[str, Device] = field(default_factory=dict)
    host: list = field(default_factory=list)      # [name, start, dur]

    # ---- (de)serialisation for the recorded test trace --------------------
    def to_json(self) -> dict:
        return {"devices": {k: {"modules": d.modules, "ops": d.ops}
                            for k, d in self.devices.items()},
                "host": self.host}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(devices={k: Device(modules=v["modules"], ops=v["ops"])
                            for k, v in d["devices"].items()},
                   host=d["host"])

    @classmethod
    def read_json(cls, path: Path) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))

    # ---- the window ------------------------------------------------------
    def span(self, name: str) -> tuple[int, int]:
        """(start, end) in ns of the first host span called ``name``."""
        for n, s, d in self.host:
            if n == name:
                return s, s + d
        raise KeyError(f"no host span {name!r} in the trace")

    def device_names(self) -> list[str]:
        return sorted(self.devices, key=_device_order)

    def trimmed(self, lo: int, hi: int) -> "Trace":
        """Only the events that start in [lo, hi)."""
        def keep(evs):
            return [e for e in evs if lo <= e[1] < hi]
        return Trace(devices={k: Device(modules=keep(d.modules),
                                        ops=keep(d.ops))
                              for k, d in self.devices.items()},
                     host=[e for e in self.host
                           if e[1] < hi and e[1] + e[2] > lo])


def _device_order(name: str):
    m = re.search(r"(\d+)$", name)
    return (int(m.group(1)) if m else 0, name)


def load(path: Path) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    t = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = Device()
            for line in plane.lines:
                if line.name == MODULES:
                    dev.modules = [[e.name, int(e.start_ns), int(e.duration_ns)]
                                   for e in line.events]
                elif line.name == OPS:
                    dev.ops = [[_instruction(e.name), int(e.start_ns),
                                int(e.duration_ns)] for e in line.events]
            if dev.ops or dev.modules:
                t.devices[plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                t.host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                              for e in line.events
                              if e.name.startswith("bench."))
    t.host.sort(key=lambda e: e[1])
    return t


def _instruction(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = re.match(r"%?([^\s=]+)\s*=", text)
    return m.group(1) if m else text.split(" ", 1)[0]


def kind(name: str) -> str:
    """An instruction's kind: its name without the numeric suffix."""
    return re.sub(r"(\.\d+)+$", "", name)


def find_xplane(root: Path) -> Path:
    found = sorted(Path(root).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return found[-1]


# ---- reductions -------------------------------------------------------------
def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged [start, end) intervals, clipped to [lo, hi)."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d, *_ in intervals
                if s < hi and s + d > lo)
    out: list[tuple[int, int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def busy_ns(dev: Device, lo: int, hi: int) -> int:
    """Nanoseconds in [lo, hi) in which an operation ran on the device."""
    return sum(e - s for s, e in union(dev.ops, lo, hi))


def program_time(dev: Device, pattern: str, lo: int, hi: int
                 ) -> tuple[float, int]:
    """(summed seconds, executions) of the compiled programs whose name
    matches ``pattern``, counting those that start in [lo, hi)."""
    rx = re.compile(pattern)
    ev = [e for e in dev.modules if lo <= e[1] < hi and rx.search(e[0])]
    return sum(e[2] for e in ev) * 1e-9, len(ev)


def op_time(dev: Device, op_kind: str, lo: int, hi: int
            ) -> tuple[float, int]:
    """(summed seconds, count) of the device ops of kind ``op_kind`` (a
    Pallas kernel's function name) that start in [lo, hi)."""
    ev = [e for e in dev.ops if lo <= e[1] < hi and kind(e[0]) == op_kind]
    return sum(e[2] for e in ev) * 1e-9, len(ev)


def top_ops(dev: Device, lo: int, hi: int, n: int = 10) -> list:
    """The ``n`` op kinds that took the most device time, as ``[kind,
    seconds]``; loops and calls, which contain other ops, are left out."""
    acc: dict[str, int] = {}
    for name, s, d in dev.ops:
        k = kind(name)
        if lo <= s < hi and k not in CONTAINERS:
            acc[k] = acc.get(k, 0) + d
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def idle_gaps(trace: Trace, dev: Device, lo: int, hi: int, n: int = 10
              ) -> list:
    """The ``n`` longest stretches with no device op, as ``[name,
    seconds]``, each named by the benchmark's host span it falls in."""
    busy = union(dev.ops, lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    spans = [h for h in trace.host if h[0] != "bench.window"]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        name = next((h[0] for h in spans if h[1] <= mid < h[1] + h[2]),
                    "bench.window")
        out.append([name, (e - s) * 1e-9])
    return out


def describe(path: Path, n: int = 5) -> str:
    """Planes, lines and the first events (with stats) of a raw trace: what
    to look at by hand before trusting the reduction."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:n]:
                out.append(f"    {e.name!r} start {e.start_ns} dur "
                           f"{e.duration_ns} stats {dict(e.stats)}")
    return "\n".join(out)


def main(argv=None) -> int:
    """``python3 bench/tracing.py <trace dir> <out.json.gz> [steps]``: reduce
    the newest ``.xplane.pb`` under the directory, print what it holds, and
    write the reduced trace, cut to its first ``steps`` decode steps inside
    the benchmark window (all of it when not given), as gzipped JSON."""
    import sys

    args = list(sys.argv[1:] if argv is None else argv)
    raw = find_xplane(Path(args[0]))
    print(describe(raw))
    t = load(raw)
    if len(args) > 2:
        lo, hi = t.span("bench.window")
        dev = t.devices[t.device_names()[0]]
        starts = sorted(e[1] for e in dev.modules
                        if lo <= e[1] < hi and "_decode_all" in e[0])
        k = int(args[2])
        if len(starts) > k:
            hi = starts[k]
        t = t.trimmed(lo, hi)
        t.host = [h for h in t.host if h[0] != "bench.window"]
        t.host.append(["bench.window", lo, hi - lo])
        t.host.sort(key=lambda e: e[1])
    with gzip.open(args[1], "wt") as f:
        json.dump(t.to_json(), f)
    return 0


if __name__ == "__main__":
    import sys as _sys
    _sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    raise SystemExit(main())
