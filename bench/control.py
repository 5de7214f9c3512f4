#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3 \
        [--control program|reference|""]

For each seed, in one process: the cell is set up and served for
``--seconds`` at its own load, exactly as ``run.py`` does, and the sample
``run.py`` would take is compared with the float32 reference (the
program's readings: the lower reading). Then the control, at the precision
below the configuration's bf16, over the same prompts and served tokens:
at every position the reference's gap of the token the control puts first
(the upper reading). ``program`` (the default) switches on the program's
own int8 weight path (``weight_dtype="int8"``) teacher-forced;
``reference`` runs the reference with its weights rounded to int8, for a
model whose bf16 and int8 weights do not fit on one chip together. Both
readings go through the harness's own verdict with the cell's limits
(``bench/limits/<cell>.json``): the program's has to come out correct, the
control's not. Prints one JSON line per seed. ``bench/tests`` keeps the
control at a tiny size.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def control_choices(params, cfg, prompt, served) -> np.ndarray:
    """Tokens the program's int8 weight path puts first at every position
    of ``prompt`` + ``served`` (teacher-forced)."""
    import jax
    import jax.numpy as jnp

    from repro.models import forward, logits_fn
    from repro.quant import quantize_params

    cq = dataclasses.replace(cfg, weight_dtype="int8")
    pq = quantize_params(params, cq)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1, len(seq))

    @jax.jit
    def first(p, toks):
        h, _, _ = forward(p, cq, toks[None])
        return jnp.argmax(logits_fn(p, cq, h[:, jnp.asarray(pos)]), -1)[0]

    out = np.asarray(first(pq, jnp.asarray(seq)))
    del pq
    return out


def reference_int8_choices(a, w, c, prompt, served) -> np.ndarray:
    """Tokens the reference with int8-rounded weights (the architecture
    module ``a``'s ``hidden(..., quant=True)`` and the int8 head) puts
    first at every position of ``prompt`` + ``served``: the control where
    the program's own int8 path cannot hold its weights twice on one
    chip."""
    from bench import reference

    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(served[:-1], np.int32)])
    pos = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    _, best = reference.logit_gaps(a, w, c, seq, pos, np.zeros_like(pos),
                                   quant=True)
    return best


def judge(a, w, c, samples, limits: dict, control: str, *, params=None,
          cfg=None, failed: int = 0, finished: int = 1) -> dict:
    """The program's readings over ``samples`` (``(prompt, served tokens)``
    pairs) and the control's over the same positions, each with the
    verdict of :func:`bench.check.verdict` under ``limits``; ``a`` is the
    architecture's module (:func:`bench.arch.load`)."""
    from bench import check, reference

    gp, gc_ = [], []
    for prompt, toks in samples:
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(toks[:-1], np.int32)])
        pos = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
        chosen = [np.asarray(toks, np.int32)]
        if control == "program":
            chosen.append(control_choices(params, cfg, prompt, toks))
        elif control == "reference":
            chosen.append(reference_int8_choices(a, w, c, prompt, toks))
        g, _ = reference.logit_gaps(a, w, c, seq, pos, np.stack(chosen))
        g = np.atleast_2d(g)
        gp.append(g[0])
        if control:
            gc_.append(g[1])
    out = {}
    for who, gaps in (("program", gp), ("control", gc_)):
        if who == "control" and not control:
            continue
        r = check.summarize(gaps)
        ok, checks = check.verdict(r, limits, failed=failed,
                                   finished=finished)
        out[who] = {"correct": ok, "readings": r,
                    "checks": {k: v["value"] for k, v in checks.items()}}
    return out


def readings(root: Path, workload: str, seconds: float, seed: int, *,
             control: str = "program", require_chip: bool = True) -> dict:
    """The program's readings on one seed and the control's (``"program"``:
    its int8 weight path; ``"reference"``: the reference at int8; ``""``:
    none), each with its verdict under the cell's limits."""
    from bench import check, run, serve

    cell = run.load_cell(root, workload)
    devices = run._chip_check(cell, require_chip)
    su = run.set_up(cell, seed, seconds, devices)
    win = serve.run_window(su.engine, su.mix, seconds, su.sizing.clients,
                           primed=su.primed)
    e2e = serve.end_to_end(su.engine, win, su.mix.open_loop)
    samples = check.pick_sample(su.engine, win.records, seed)
    su.engine.cache = su.engine.params = None
    su.engine = None
    gc.collect()
    out = judge(cell.arch, su.w, cell.config, samples,
                check.load_limits(root, workload), control, params=su.params,
                cfg=cell.arch.program_config(cell.config),
                failed=e2e["failed"], finished=e2e["finished"])
    return {"seed": seed, "clients": su.sizing.clients,
            "max_slots": su.sizing.max_slots, **out}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", choices=("program", "reference", ""),
                    default="program",
                    help="the program's int8 weight path, the reference "
                         "at int8, or none")
    args = ap.parse_args(argv)

    import jax

    from bench import run

    run._enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(ROOT, args.workload, args.seconds, seed,
                     control=args.control)
        print(json.dumps(r), flush=True)
        gc.collect()
        jax.clear_caches()
    return 0


if __name__ == "__main__":
    sys.exit(main())
