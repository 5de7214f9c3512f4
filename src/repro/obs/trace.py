"""Ring-buffered request-lifecycle tracer with Chrome-trace export.

The engine emits structured events at every lifecycle edge — submit,
queue-skip/aging, admission, per-chunk prefill, handoff, dispatch vs sync
under ``overlap_decode``, preempt/requeue/resume, spec propose/accept/
rollback, COW fork, finish. Events are keyed by request ``uid`` and (when
placed) ``slot``; phases of a request's life are *spans* (``begin``/``end``
pairs) and point occurrences are *instants*.

Pay-for-what-you-use: a disabled tracer is :data:`NULL_TRACER`, whose
methods are empty — call sites invoke it unconditionally instead of
branching on a flag, so the hot path carries no if-forest.

The ring is on ``time.perf_counter``, which no device trace shares. The
phases of an engine step are therefore not ring events: ``span(name)``, the
same on every tracer, returns a ``jax.profiler.TraceAnnotation``, which
lands in a ``jax.profiler`` capture beside the device's ops whenever one is
recording, whichever tracer the engine holds, and costs one inactive TraceMe
otherwise. :data:`STEP_SPANS` lists their names.

Export is the Chrome trace-event JSON format (``to_chrome``), loadable in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``: spans become
async ``b``/``e`` events matched on ``(cat, id, name)`` so preempt/resume
gaps render as separate slices on the request's track, instants become
``i`` events on the slot's thread track. :func:`validate_chrome_trace`
checks the structural contract CI relies on — balanced begin/end pairs and
a closed ``request`` span for every request id.
"""
from __future__ import annotations

import json
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable

from jax.profiler import TraceAnnotation

__all__ = [
    "NULL_TRACER",
    "NullTracer",
    "STEP_SPANS",
    "TraceEvent",
    "Tracer",
    "validate_chrome_trace",
]

#: span names in nesting order (outermost first)
SPANS = ("request", "queue", "prefill", "decode")

#: instant event catalogue (see docs/observability.md for the schema)
INSTANTS = (
    "submit", "queue_skip", "aged", "admit", "reject", "prefill_chunk",
    "handoff", "handoff_wait", "dispatch", "sync", "preempt", "requeue",
    "spec_propose", "spec_commit", "spec_rollback", "cow", "fork",
    "finish", "truncate",
)

#: profiler spans of ``ServeEngine.step``: the step, then its phases, each
#: a child of ``serve.step`` (see docs/observability.md)
STEP_SPANS = (
    "serve.step", "serve.admit", "serve.prefill", "serve.decode.dispatch",
    "serve.decode.sync", "serve.decode.emit", "serve.account", "serve.spec",
)


def span(name: str, **args: Any) -> TraceAnnotation:
    """A profiler span for ``with``: recorded, with ``args`` as its stats,
    while a ``jax.profiler`` trace is active; one inactive TraceMe else."""
    return TraceAnnotation(name, **args)


def _scalar(v: Any) -> Any:
    """Coerce numpy scalars (slot indices, summed counters) to JSON types."""
    return v.item() if hasattr(v, "item") else v


@dataclass(frozen=True)
class TraceEvent:
    name: str
    ph: str                      # "i" instant | "b" span begin | "e" span end
    ts: float                    # clock() seconds (perf_counter by default)
    uid: int | None = None
    slot: int | None = None
    args: tuple[tuple[str, Any], ...] = ()


class Tracer:
    """Bounded in-memory event recorder. ``buffer`` caps retained events;
    overflow drops the oldest and counts into ``dropped`` (exported as
    metadata so validators know the record is partial)."""

    enabled = True

    def __init__(self, buffer: int = 65536,
                 clock: Callable[[], float] = time.perf_counter):
        if buffer < 1:
            raise ValueError("trace buffer must hold >= 1 event")
        self.clock = clock
        self.dropped = 0
        self._ev: deque[TraceEvent] = deque(maxlen=int(buffer))
        # uid -> stack of open span names (LIFO close order)
        self._open: dict[int, list[str]] = {}

    # ---- recording -------------------------------------------------------
    def _push(self, ev: TraceEvent) -> None:
        if len(self._ev) == self._ev.maxlen:
            self.dropped += 1
        self._ev.append(ev)

    def event(self, name: str, uid: int | None = None,
              slot: int | None = None, **args: Any) -> None:
        self._push(TraceEvent(name, "i", self.clock(), uid, slot,
                              tuple(args.items())))

    def begin(self, span: str, uid: int, slot: int | None = None,
              **args: Any) -> None:
        self._open.setdefault(uid, []).append(span)
        self._push(TraceEvent(span, "b", self.clock(), uid, slot,
                              tuple(args.items())))

    def end(self, span: str, uid: int, slot: int | None = None,
            **args: Any) -> None:
        stack = self._open.get(uid)
        if stack and span in stack:
            stack.remove(span)
            if not stack:
                del self._open[uid]
        self._push(TraceEvent(span, "e", self.clock(), uid, slot,
                              tuple(args.items())))

    def close_open(self, uid: int, keep: tuple[str, ...] = (),
                   slot: int | None = None, **args: Any) -> None:
        """End every span still open for ``uid`` (innermost first), except
        names in ``keep`` — preemption closes phase spans but keeps the
        request span alive across the requeue."""
        stack = self._open.get(uid, [])
        for span in [s for s in reversed(stack) if s not in keep]:
            self.end(span, uid, slot=slot, **args)

    def open_spans(self, uid: int) -> tuple[str, ...]:
        return tuple(self._open.get(uid, ()))

    span = staticmethod(span)

    # ---- reading / export ------------------------------------------------
    def events(self) -> list[TraceEvent]:
        return list(self._ev)

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON object format."""
        evs = self.events()
        t0 = min((e.ts for e in evs), default=0.0)
        out: list[dict] = [
            {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
             "args": {"name": "repro.serve"}},
        ]
        seen_tids: set[int] = set()
        for e in evs:
            tid = int(e.slot) if e.slot is not None else -1
            seen_tids.add(tid)
            args = {k: _scalar(v) for k, v in e.args}
            if e.uid is not None:
                args.setdefault("uid", int(e.uid))
            rec: dict[str, Any] = {
                "name": e.name,
                "ph": e.ph,
                "ts": round((e.ts - t0) * 1e6, 3),
                "pid": 0,
                "tid": tid,
                "args": args,
            }
            if e.ph == "i":
                rec["s"] = "p"
            else:  # async span events match on (cat, id, name)
                rec["cat"] = "lifecycle"
                rec["id"] = str(e.uid)
            out.append(rec)
        for tid in sorted(seen_tids):
            out.append({"name": "thread_name", "ph": "M", "pid": 0,
                        "tid": tid,
                        "args": {"name": ("queue/engine" if tid < 0
                                          else f"slot {tid}")}})
        return {
            "traceEvents": out,
            "displayTimeUnit": "ms",
            "otherData": {"dropped": self.dropped,
                          "clock": getattr(self.clock, "__name__",
                                           str(self.clock))},
        }

    def export(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f, indent=1)

    def __len__(self) -> int:
        return len(self._ev)


class NullTracer:
    """No-op tracer with the full :class:`Tracer` surface. Engine code calls
    ``self.trace.event(...)`` unconditionally; disabled tracing costs one
    empty method call, not a branch per site."""

    enabled = False
    dropped = 0

    def event(self, name, uid=None, slot=None, **args):
        pass

    def begin(self, span, uid, slot=None, **args):
        pass

    def end(self, span, uid, slot=None, **args):
        pass

    def close_open(self, uid, keep=(), slot=None, **args):
        pass

    def open_spans(self, uid):
        return ()

    span = staticmethod(span)

    def events(self):
        return []

    def to_chrome(self):
        return {"traceEvents": [], "displayTimeUnit": "ms",
                "otherData": {"dropped": 0}}

    def export(self, path):
        pass

    def __len__(self):
        return 0

    def __bool__(self):
        return False


#: shared disabled tracer — the default hook on every engine
NULL_TRACER = NullTracer()


def validate_chrome_trace(doc: Any) -> dict:
    """Validate a Chrome-trace JSON object against the contract the engine
    guarantees; raises ``ValueError`` on violation, returns a summary.

    Checks: structural shape (object format, required keys per phase type),
    monotone non-negative ``ts``, balanced async begin/end per
    ``(cat, id, name)`` with begin-before-end, and a *closed* ``request``
    span for every request id that has any lifecycle event.
    """
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        raise ValueError("not a Chrome trace object: missing traceEvents")
    evs = doc["traceEvents"]
    if not isinstance(evs, list):
        raise ValueError("traceEvents must be a list")

    open_spans: dict[tuple[str, str, str], list[float]] = {}
    request_ids: set[str] = set()
    closed_requests: set[str] = set()
    n_spans = n_instants = 0
    for i, e in enumerate(evs):
        if not isinstance(e, dict) or "ph" not in e or "name" not in e:
            raise ValueError(f"event {i}: missing ph/name")
        ph = e["ph"]
        if ph == "M":
            continue
        for k in ("ts", "pid", "tid"):
            if k not in e:
                raise ValueError(f"event {i} ({e['name']}): missing {k!r}")
        if e["ts"] < 0:
            raise ValueError(f"event {i} ({e['name']}): negative ts")
        if ph == "i":
            n_instants += 1
            continue
        if ph not in ("b", "e"):
            raise ValueError(f"event {i}: unknown phase type {ph!r}")
        if "cat" not in e or "id" not in e:
            raise ValueError(
                f"event {i} ({e['name']}): async span missing cat/id")
        key = (e["cat"], str(e["id"]), e["name"])
        request_ids.add(str(e["id"]))
        if ph == "b":
            n_spans += 1
            open_spans.setdefault(key, []).append(e["ts"])
        else:
            stack = open_spans.get(key)
            if not stack:
                raise ValueError(
                    f"event {i}: orphan end for span {key} (no open begin)")
            begin_ts = stack.pop()
            if e["ts"] < begin_ts:
                raise ValueError(
                    f"event {i}: span {key} ends before it begins")
            if e["name"] == "request":
                closed_requests.add(str(e["id"]))

    orphans = {k: len(v) for k, v in open_spans.items() if v}
    if orphans:
        raise ValueError(f"orphan begin events (never ended): {orphans}")
    unclosed = request_ids - closed_requests
    if unclosed:
        raise ValueError(
            f"request ids without a closed 'request' span: {sorted(unclosed)}")
    return {
        "events": len(evs),
        "spans": n_spans,
        "instants": n_instants,
        "requests": len(closed_requests),
        "dropped": (doc.get("otherData") or {}).get("dropped", 0),
    }
