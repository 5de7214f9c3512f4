#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Everything a cell is made of is found by name from ``BENCHMARK.json``: its
configuration file (``configs[].file``), the module of that configuration's
architecture (``bench/arch/<model_type>.py``: weight layout, the program's
configuration, the float32 reference and the cost counts), its traffic mix
(``bench/traffic/<traffic>.json``), its correctness limit
(``bench/limits/<cell>.json``) and, with ``--trace 1``, one reader per
per-layer metric (``bench/metrics/<metric>.py``, a ``read(ctx)`` that
returns a number or ``None``). A new cell, mix, configuration, architecture
or metric is new files and entries; this file does not change.

A run makes the weights from the seed on the device, sizes the KV pool as
the largest whose compiled steps fit the chip (the compiler's
``memory_analysis``), builds the program's ``ServeEngine``, warms up every
program the traffic uses and, for a closed loop, starts its clients
part-way through their first requests (set-up, timed as ``setup_s`` from
process start); then it offers the mix for ``--seconds`` and takes the
end-to-end numbers from the client's side; with ``--trace 1`` the profiler
records the same window and the per-layer readers reduce it. Then the
engine's state is freed and a sample of what it finished is compared with
the float32 reference. Without a TPU (or with fewer chips than the cell
asks for) it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import arch  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_BAD_TREE = 2


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""
    name: str
    chips: int
    config: dict            # the configuration file's contents
    arch: object            # its architecture's module (bench.arch.load)
    traffic: dict           # the traffic mix's parameters
    end_to_end: list        # metric entries this cell reports, trace 0
    per_layer: list         # metric entries this cell reports, trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(by_name)})")
    w = by_name[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" /
                          f"{w['traffic']}.json").read_text())
    return Cell(name=name, chips=int(w["chips"]), config=config,
                arch=arch.load(config, root), traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def load_reader(root: Path, metric: str):
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class Context:
    """What a per-layer reader may read: the traced window's trace, the
    program's counters over it, and the sizes of the cell."""
    trace: object                   # bench.tracing.Trace
    lo: int                         # traced window, trace ns
    hi: int
    counters: dict                  # counter deltas over the window
    shape: object                   # the architecture's cost object
    peaks: object                   # bench.costs.Peaks
    engine: dict                    # max_slots, max_len, chunk, page
    requests: list                  # per request: prompt, first token ts...
    chips: int
    extra: dict = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def device0(self):
        return self.trace.devices[self.trace.device_names()[0]]

    def used_devices(self) -> list:
        names = self.trace.device_names()[:self.chips]
        return [self.trace.devices[n] for n in names]


def _chip_check(cell: Cell, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < cell.chips):
        raise NoChip(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX "
                     f"found {len(devs)} x {devs[0].platform} "
                     f"{devs[0].device_kind}")
    return devs[:cell.chips]


def _enable_cache() -> str:
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def _partitioner(cfg, devices):
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs.base import StrategyConfig
    from repro.core.sharding import Partitioner

    mesh = Mesh(np.asarray(devices).reshape(1, len(devices)),
                ("data", "model"))
    return Partitioner(mesh, StrategyConfig(name="ramora",
                                            tensor_parallel=True),
                       cfg, mode="serve")


@dataclass
class Served:
    """A cell set up and ready for its window."""
    w: dict                 # the benchmark's weights
    params: object          # the same arrays in the program's layout
    engine: object
    mix: object             # bench.traffic.Mix
    sizing: object          # bench.serve.Sizing
    primed: list            # closed loop: the clients' first requests
    bytes_limit: int


def set_up(cell: Cell, seed: int, seconds: float, devices,
           bytes_limit: int | None = None, fault=None) -> Served:
    """Weights from the seed, the pool sized from the compiled steps, the
    engine, every program the traffic uses warmed up and, for a closed
    loop, its clients started. ``bytes_limit`` stands in for the chip's
    where the device reports none (a test on the CPU); ``fault``, a test's
    hook, is called with the built engine."""
    import jax

    from bench import model, serve
    from bench import traffic as traffic_mod

    c, a = cell.config, cell.arch
    shape = a.shape(c)
    cfg = a.program_config(c)
    w = model.make_weights(a.weight_shapes(c), seed, device=devices[0])
    params = a.to_program(w, c)
    model.check_program_layout(params, cfg)
    part = _partitioner(cfg, devices) if cell.chips > 1 else None
    if bytes_limit is None:
        bytes_limit = int((devices[0].memory_stats() or {})["bytes_limit"])

    def need(sz):
        probe = serve.make_engine(cfg, c, params, serve.Sizing(
            n_blocks=sz.max_slots + 1, max_slots=sz.max_slots, clients=0,
            pool_bytes=0), seed, part=part)
        return serve.step_bytes(probe, params, sz)

    sizing = serve.size_pool(shape, c, cell.traffic, bytes_limit, need,
                             kv_shard=cell.chips)
    engine = serve.make_engine(cfg, c, params, sizing, seed, part=part)
    if fault is not None:
        fault(engine)
    mix = traffic_mod.generate(cell.traffic, seed, seconds, shape.vocab,
                               clients=sizing.clients)
    serve.warm_up(engine, mix, shape.vocab, seed)
    primed = ([] if mix.open_loop
              else serve.prime(engine, mix, sizing.clients))
    jax.effects_barrier()
    return Served(w=w, params=params, engine=engine, mix=mix, sizing=sizing,
                  primed=primed, bytes_limit=bytes_limit)


def run(args, root: Path = ROOT, require_chip: bool = True,
        fault=None, bytes_limit: int | None = None) -> dict:
    """One run of one cell; returns the result line's object. ``fault``
    and ``bytes_limit`` are tests' hooks (see :func:`set_up`)."""
    import jax

    from bench import check, costs, serve, tracing

    cell = load_cell(root, args.workload)
    devices = _chip_check(cell, require_chip)
    c = cell.config
    shape = cell.arch.shape(c)
    kind = devices[0].device_kind

    # ---- set-up: weights, pool, engine, warm-up, clients ----------------
    su = set_up(cell, args.seed, args.seconds, devices, bytes_limit, fault)
    engine, sizing = su.engine, su.sizing
    watch = serve.HostWatch()

    # ---- the measured window --------------------------------------------
    trace_dir = root / "bench" / ".out" / "trace" / cell.name
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    before = engine.metrics.snapshot()
    setup_s = time.perf_counter() - T_START
    win = serve.run_window(engine, su.mix, args.seconds, sizing.clients,
                           primed=su.primed, watch=watch)
    after = engine.metrics.snapshot()
    if args.trace:
        jax.effects_barrier()
        jax.profiler.stop_trace()
    watch.stop()
    e2e = serve.end_to_end(engine, win, su.mix.open_loop)

    # ---- take the sample, read memory, free the engine -------------------
    served = check.pick_sample(engine, win.records, args.seed)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    requests = [{"prompt": r.prompt_len, "max_new": r.max_new,
                 "prefix": r.item.prefix,
                 "tokens_ts": list(engine.results[r.uid].token_ts)}
                for r in win.records]
    counters = dict(after.delta(before).counters)
    engine_info = {"max_slots": engine.max_slots, "max_len": engine.max_len,
                   "chunk": engine.prefill_chunk, "page": engine.page_size,
                   "n_blocks": engine.n_blocks}
    engine.cache = engine.params = None
    su.engine = su.params = engine = None
    gc.collect()

    # ---- per-layer metrics from the trace --------------------------------
    metrics: dict = {}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        tr = tracing.load(tracing.find_xplane(trace_dir))
        lo, hi = tr.span("bench.window")
        ctx = Context(trace=tr, lo=lo, hi=hi, counters=counters, shape=shape,
                      peaks=costs.peaks(kind), engine=engine_info,
                      requests=requests, chips=cell.chips,
                      extra={"t0": win.t0, "t1": win.t1,
                             "prefix_tokens": (cell.traffic.get(
                                 "shared_prefix") or {}).get("tokens", 0)})
        used = ctx.used_devices()
        device["busy_s"] = sum(tracing.busy_ns(d, lo, hi)
                               for d in used) * 1e-9 / len(used)
        device["window_s"] = ctx.window_s
        for m in cell.per_layer:
            v = load_reader(root, m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = {"device_ops": tracing.top_ops(ctx.device0, lo, hi),
                     "idle_gaps": tracing.idle_gaps(tr, ctx.device0, lo, hi)}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    # ---- correctness -----------------------------------------------------
    readings = check.compare(cell.arch, su.w, c, served)
    correct, checks = check.verdict(readings, check.load_limits(
        root, cell.name), failed=e2e["failed"], finished=e2e["finished"])
    out = {"correct": bool(correct), "attempted": e2e["attempted"],
           "failed": e2e["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["info"] = {"setup_s": setup_s,
                   "bytes_limit": su.bytes_limit,
                   "max_slots": sizing.max_slots, "n_blocks": sizing.n_blocks,
                   "pool_bytes": sizing.pool_bytes,
                   "need_bytes": sizing.need_bytes,
                   "clients": sizing.clients, "steps": win.steps,
                   "late_s": win.late_s, "window_s": win.t1 - win.t0,
                   "compiles_in_window": watch.compiles,
                   "jax_events_in_window": watch.events,
                   "gc_s_in_window": win.gc_s,
                   "gc_collections_in_window": watch.gc_n,
                   "slow_steps": win.slow_steps,
                   "readings": readings,
                   "e2e": {k: v for k, v in e2e.items()
                           if k not in ("attempted", "failed")}}
    out["checks"] = checks
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src' / 'repro'}",
              file=sys.stderr)
        return EXIT_BAD_TREE
    sys.path.insert(0, str(ROOT / "src"))
    try:
        print(f"bench: compile cache {_enable_cache()}", file=sys.stderr)
        out = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_NO_CHIP
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r}, "
              f"{'ok' if v['ok'] else 'FAILED'})", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
