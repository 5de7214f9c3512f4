"""Drive the program's ``ServeEngine`` through one measured window.

The benchmark talks to the engine only through ``submit`` and ``step``, its
``Result`` objects (tokens and their host timestamps) and its metrics
registry. Its own host spans (``bench.window``, ``bench.step``,
``bench.submit``, ``bench.wait``) go into the profiler's trace when one is
recording, so idle device time can be named by what the host was doing.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field

import numpy as np

from bench import traffic as traffic_mod


#: share of the chip's memory kept out of the pool beyond what the compiled
#: steps need: buffers that live outside them (block tables, sampled ids,
#: the next step's inputs) and the allocator's fragmentation
HEADROOM = 0.03


@dataclass
class Sizing:
    n_blocks: int
    max_slots: int
    clients: int            # closed loop: concurrent callers (0: open loop)
    pool_bytes: int
    need_bytes: int = 0     # the compiled steps' peak at this pool, fitted


def at_blocks(s, c: dict, params: dict, n_blocks: int, kv_shard: int = 1
              ) -> Sizing:
    """The slots a pool of ``n_blocks`` holds at this traffic's mean
    reservation (open loop) or at its largest (closed loop, one slot per
    client). ``s`` is the architecture's cost object (its
    ``kv_token_bytes``)."""
    page = int(c["engine"]["page_size"])
    block_bytes = page * s.kv_token_bytes // kv_shard
    usable = n_blocks - 1                       # block 0 is the null block
    if params["loop"] == "closed":
        per = math.ceil(traffic_mod.largest_reservation(params) / page)
        clients = usable // per
        slots = clients
    else:
        clients = 0
        shared = math.ceil(traffic_mod.shared_tokens(params) / page)
        per = math.ceil(traffic_mod.mean_fresh_tokens(params) / page)
        slots = (usable - shared) // per
    return Sizing(n_blocks=int(n_blocks), max_slots=int(slots),
                  clients=int(clients), pool_bytes=int(n_blocks * block_bytes))


def size_pool(s, c: dict, params: dict, bytes_limit: int, need,
              kv_shard: int = 1) -> Sizing:
    """The largest KV pool whose compiled steps fit the chip's memory.

    ``need(sizing)`` is the device bytes the step programs take at a pool
    and slot count (:func:`step_bytes`: weights, pool, temporaries, as the
    compiler's ``memory_analysis`` reports them). It is read at two small
    pools, where every program compiles, and is affine in the pool; the
    pool is the largest whose fitted need leaves ``HEADROOM`` of
    ``bytes_limit`` free. ``s`` is the architecture's cost object (its
    ``kv_token_bytes`` and ``weight_bytes``)."""
    block_bytes = int(c["engine"]["page_size"]) * s.kv_token_bytes // kv_shard
    budget = int(bytes_limit * (1 - HEADROOM))
    top = (budget - s.weight_bytes) // block_bytes
    probes = []
    for n in (top // 8, top // 4):
        sz = at_blocks(s, c, params, n, kv_shard)
        if sz.max_slots < 1:
            raise ValueError(f"a pool of {n} blocks of {block_bytes} B holds"
                             f" no request of this traffic")
        probes.append((n, need(sz)))
    (n1, b1), (n2, b2) = probes
    slope = (b2 - b1) / (n2 - n1)
    n = n2 + int((budget - b2) // slope)
    sz = at_blocks(s, c, params, n, kv_shard)
    if sz.max_slots < 1:
        raise ValueError(f"the KV pool ({n} blocks of {block_bytes} B) "
                         f"holds no request of this traffic")
    sz.need_bytes = int(b2 + slope * (n - n2))
    return sz


def step_bytes(probe, params, sizing: Sizing) -> int:
    """Peak device bytes of the engine's decode and chunked-prefill
    programs at ``sizing``'s pool and slots: arguments (weights, pool, the
    step's inputs) plus outputs not aliased to them plus temporaries and
    code, from the compiler's ``memory_analysis``. ``probe`` is an engine
    of the same configuration with ``sizing.max_slots`` slots; nothing is
    allocated."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.models.cache import init_cache

    B = sizing.max_slots
    cache = jax.eval_shape(functools.partial(
        init_cache, probe.cfg, B, probe.max_len, n_blocks=sizing.n_blocks,
        page_size=probe.page_size))
    S = jax.ShapeDtypeStruct
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    tables = S((B, probe.n_pages), i32)
    progs = (
        (probe._decode_all, (
            params, cache, S((B, 1), i32), S((B,), i32), S((B,), jnp.bool_),
            tables, S((B,), f32), S((B,), i32), S((B,), f32),
            S((B, 2), u32), S((B,), u32))),
        (probe._chunk_step, (
            params, cache, S((1, probe.prefill_chunk), i32), S((), i32),
            S((), i32), S((), i32), tables, S((), f32), S((), i32),
            S((), f32), S((2,), u32), S((), u32), S((), i32))),
    )
    peak = 0
    with probe._kernel_scope():
        for fn, args in progs:
            m = jax.jit(fn, donate_argnums=(1,)).lower(
                *args).compile().memory_analysis()
            if m is None:
                raise RuntimeError("the compiler gives no memory_analysis")
            peak = max(peak, m.argument_size_in_bytes
                       + m.output_size_in_bytes - m.alias_size_in_bytes
                       + m.temp_size_in_bytes
                       + m.generated_code_size_in_bytes)
    return int(peak)


def make_engine(cfg, c: dict, params, sizing: Sizing, seed: int, part=None):
    from repro.serve import ServeEngine

    eng = c["engine"]
    return ServeEngine(cfg, params, max_slots=sizing.max_slots,
                       max_len=int(eng["max_len"]), paged=True,
                       page_size=int(eng["page_size"]),
                       prefill_chunk=int(eng["prefill_chunk"]),
                       max_blocks=sizing.n_blocks,
                       prefix_cache=bool(eng["prefix_cache"]),
                       kernel_backend=eng["kernel_backend"], seed=seed,
                       part=part)


def _request(uid: int, prompt, max_new: int):
    from repro.serve import Request
    return Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                   max_new_tokens=int(max_new), temperature=0.0)


def drain(engine, uids: list[int], limit_s: float) -> bool:
    """Step until every request in ``uids`` has finished; False on time."""
    t_end = time.perf_counter() + limit_s
    while time.perf_counter() < t_end:
        if all(engine.results[u].finish_reason for u in uids):
            return True
        engine.step()
    return all(engine.results[u].finish_reason for u in uids)


def warm_up(engine, mix: traffic_mod.Mix, vocab: int, seed: int) -> None:
    """Compile every program the window runs: a chunked prefill of more
    than one chunk, decode and sampling; then pre-fill the mix's system
    prompts (the prefix cache holds them when the window opens) and serve
    one request that adopts one of them."""
    rng = np.random.default_rng(seed ^ 0x5EED)
    uids = []
    n = engine.prefill_chunk + 3
    for i in range(2):
        uid = -1000 - i
        engine.submit(_request(uid, rng.integers(0, vocab, n), 4))
        uids.append(uid)
    for k, pre in enumerate(mix.prefixes):
        uid = -2000 - k
        engine.submit(_request(uid, pre, 1))
        uids.append(uid)
    if not drain(engine, uids, 900.0):
        raise RuntimeError("warm-up requests did not finish")
    if mix.prefixes:
        uid = -3000
        engine.submit(_request(uid, np.concatenate(
            [mix.prefixes[0], rng.integers(0, vocab, 5)]), 2))
        if not drain(engine, [uid], 300.0):
            raise RuntimeError("warm-up prefix request did not finish")
    for u in list(engine.results):
        if u < 0:
            del engine.results[u]


@dataclass
class Record:
    """What the client saw of one request."""
    uid: int
    due: float                      # absolute perf_counter time it was due
    prompt_len: int
    max_new: int
    item: traffic_mod.Item


@dataclass
class Window:
    t0: float
    t1: float
    records: list[Record] = field(default_factory=list)
    steps: int = 0
    late_s: float = 0.0             # how late the generator submitted, max
    slow_steps: list = field(default_factory=list)
    gc_s: float = 0.0               # host seconds in garbage collection


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def prime(engine, mix: traffic_mod.Mix, clients: int, uid0: int = 0,
          limit_s: float = 600.0) -> list[Record]:
    """Start a closed loop in its steady state: client ``i`` of ``C`` is
    part-way through its first request, with ``(i + 0.5) / C`` of that
    request's output still to come, as clients of a loop that has run for
    a while are. The prompts are prefilled here, in set-up, so the window
    opens on a full batch whose requests finish, and are followed by the
    mix's next ones, at staggered times inside it."""
    n = min(clients, len(mix.items))
    recs = []
    for i in range(n):
        it = mix.items[i]
        left = max(1, round(it.max_new * (i + 0.5) / n))
        engine.submit(_request(uid0 + i, it.prompt, left))
        recs.append(Record(uid=uid0 + i, due=time.perf_counter(),
                           prompt_len=len(it.prompt), max_new=left, item=it))
    t_end = time.perf_counter() + limit_s
    while not all(engine.results[r.uid].tokens for r in recs):
        if time.perf_counter() > t_end:
            raise RuntimeError("the closed loop's first requests did not "
                               "reach their first token in set-up")
        engine.step()
    return recs


class HostWatch:
    """What the host did besides stepping, inside the window: garbage
    collections, JAX's compile and compile-cache events, the process's CPU
    time, context switches and page faults, and where the main thread was
    when a step ran long, so that a slow step can be named by its cause."""

    #: a step running this long gets the main thread's stack sampled
    STALL_S = 0.4

    def __init__(self):
        import gc
        import threading

        import jax

        self.on = True
        self.gc_s = 0.0
        self.gc_n = [0, 0, 0]
        self.events: dict[str, int] = {}
        self.compiles = 0
        self.stacks: dict[int, list] = {}   # step -> [(seconds in, frames)]
        self._gc_t = 0.0
        self._step = -1
        self._step_t0 = None
        self._main = threading.get_ident()

        def on_gc(phase, info):
            if not self.on:
                return
            if phase == "start":
                self._gc_t = time.perf_counter()
            else:
                self.gc_s += time.perf_counter() - self._gc_t
                self.gc_n[info["generation"]] += 1

        def on_event(name, *_a, **_k):
            if self.on:
                self.events[name] = self.events.get(name, 0) + 1
                if name == "/jax/core/compile/backend_compile_duration":
                    self.compiles += 1

        self._on_gc = on_gc
        gc.callbacks.append(on_gc)
        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_event)
        self._done = threading.Event()
        self._sampler = threading.Thread(target=self._sample, daemon=True)
        self._sampler.start()

    def _sample(self) -> None:
        import sys
        import traceback

        next_at = self.STALL_S
        while not self._done.wait(0.05):
            t0, step = self._step_t0, self._step
            if t0 is None:
                next_at = self.STALL_S
                continue
            dt = time.perf_counter() - t0
            if dt < next_at:
                continue
            next_at = dt + self.STALL_S
            frame = sys._current_frames().get(self._main)
            if frame is None or step != self._step:
                continue
            frames = [f"{f.filename.rsplit('/', 2)[-1]}:{f.lineno} {f.name}"
                      for f in traceback.extract_stack(frame)[-8:]]
            self.stacks.setdefault(step, []).append((dt, frames))

    def begin(self, step: int) -> None:
        self._step = step
        self._step_t0 = time.perf_counter()

    def end(self) -> None:
        self._step_t0 = None

    def mark(self) -> tuple:
        import resource

        r = resource.getrusage(resource.RUSAGE_SELF)
        return (self.gc_s, tuple(self.gc_n), sum(self.events.values()),
                r.ru_utime + r.ru_stime, r.ru_nvcsw, r.ru_nivcsw,
                r.ru_majflt, r.ru_minflt)

    def stop(self) -> None:
        import gc

        self.on = False
        self._done.set()
        self._sampler.join(timeout=5.0)
        gc.callbacks.remove(self._on_gc)


#: slowest steps of a window kept in the run's record, with their causes
SLOW_STEPS = 5


def run_window(engine, mix: traffic_mod.Mix, seconds: float, clients: int,
               primed: list[Record] | None = None, uid0: int = 0,
               watch: HostWatch | None = None) -> Window:
    """Offer ``mix`` to the engine for ``seconds``: on its schedule (open
    loop) or from ``clients`` callers (closed loop), whose first requests
    ``primed`` are already in flight. Request ``i`` of the mix gets uid
    ``uid0 + i``."""
    items = mix.items
    win = Window(t0=0.0, t1=0.0)
    outstanding: set[int] = set()
    nxt = 0
    steps: list[tuple] = []
    chunks = engine.stats["prefill_chunks"]

    def submit(i: int, due: float) -> None:
        it = items[i]
        with _span("bench.submit"):
            engine.submit(_request(uid0 + i, it.prompt, it.max_new))
        win.records.append(Record(uid=uid0 + i, due=due,
                                  prompt_len=len(it.prompt),
                                  max_new=it.max_new, item=it))
        outstanding.add(uid0 + i)

    with _span("bench.window"):
        t0 = time.perf_counter()
        win.t0 = t0
        t1 = t0 + seconds
        if not mix.open_loop:
            for r in primed or []:
                win.records.append(r)
                outstanding.add(r.uid)
            nxt = len(win.records)
            for _ in range(min(clients, len(items)) - nxt):
                submit(nxt, t0)
                nxt += 1
        while True:
            now = time.perf_counter()
            if now >= t1:
                break
            if mix.open_loop:
                while nxt < len(items) and t0 + items[nxt].due <= now:
                    win.late_s = max(win.late_s,
                                     now - (t0 + items[nxt].due))
                    submit(nxt, t0 + items[nxt].due)
                    nxt += 1
            if outstanding:
                before = None
                if watch is not None:
                    before = watch.mark()
                    watch.begin(win.steps)
                with _span("bench.step"):
                    engine.step()
                dt = time.perf_counter() - now
                if watch is not None:
                    watch.end()
                c = engine.stats["prefill_chunks"]
                steps.append((dt, c - chunks, before,
                              watch.mark() if watch else None, win.steps,
                              now - t0))
                win.steps += 1
                chunks = c
                done = [u for u in outstanding
                        if engine.results[u].finish_reason]
                for u in done:
                    outstanding.discard(u)
                    if not mix.open_loop and nxt < len(items):
                        submit(nxt, time.perf_counter())
                        nxt += 1
            elif mix.open_loop and nxt < len(items):
                with _span("bench.wait"):
                    time.sleep(max(0.0, min(t0 + items[nxt].due, t1) - now))
            else:
                with _span("bench.wait"):
                    time.sleep(max(0.0, t1 - now))
        win.t1 = time.perf_counter()
    if watch is not None:
        win.gc_s = watch.gc_s
    for dt, n_chunks, a, b, i, at in sorted(
            steps, key=lambda x: -x[0])[:SLOW_STEPS]:
        row = {"s": dt, "step": i, "at_s": at, "prefill_chunks": n_chunks}
        if a is not None:
            row.update(gc_s=b[0] - a[0],
                       gc_collections=[y - x for x, y in zip(a[1], b[1])],
                       jax_events=b[2] - a[2], cpu_s=b[3] - a[3],
                       voluntary_switches=b[4] - a[4],
                       involuntary_switches=b[5] - a[5],
                       major_faults=b[6] - a[6], minor_faults=b[7] - a[7],
                       stacks=watch.stacks.get(i, []))
        win.slow_steps.append(row)
    return win


def _p95(values) -> float:
    v = np.sort(np.asarray(values, np.float64))
    return float(np.percentile(v, 95)) if len(v) else float("nan")


def end_to_end(engine, win: Window, open_loop: bool) -> dict:
    """The client-side numbers of one window, from the engine's results and
    the host timestamps it stamped on each token."""
    t0, t1 = win.t0, win.t1
    n_tok = 0
    gaps: list[float] = []
    ttft: list[float] = []
    failed = finished = 0
    for r in win.records:
        res = engine.results[r.uid]
        ts = np.asarray(res.token_ts, np.float64)
        inside = ts[(ts >= t0) & (ts <= t1)]
        n_tok += len(inside)
        if len(inside) > 1:
            gaps.extend(np.diff(inside).tolist())
        if open_loop:
            first = ts[0] if len(ts) else math.inf
            ttft.append(min(first, t1) - r.due)
        if res.finish_reason in ("rejected", "truncated"):
            failed += 1
        elif res.finish_reason and len(ts) and t0 <= ts[-1] <= t1:
            finished += 1
    out = {"output_tokens_per_s": n_tok / (t1 - t0),
           "itl_p95_ms": _p95(gaps) * 1e3,
           "n_tokens": n_tok, "n_gaps": len(gaps)}
    if open_loop:
        out["ttft_p95_ms"] = _p95(ttft) * 1e3
        out["n_ttft"] = len(ttft)
    out["finished"] = finished
    out["attempted"] = len(win.records)
    out["failed"] = failed
    return out
