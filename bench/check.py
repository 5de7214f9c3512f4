"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest of them, is compared with the
float32 reference: the reference reads each prompt with
the tokens the engine served by the close, and for every served token
takes the gap by which the reference's logit of that token lies below its
best logit. Each gap statistic that the cell's limits file
(``bench/limits/<cell>.json``) names is compared with its limit. Greedy
decoding in bf16 serves near-ties, so gaps are small but not all 0; a lower
precision, a token altered where it is produced, or a step computed wrong
opens them.

A window of long requests finishes few of them; where those hold fewer
than ``SAMPLE_TOKENS`` served tokens, requests still running at the close
fill the sample, the one served the most first: every served token is an
answer already given. A window that finished no request is not correct.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

#: served tokens the sample should hold at least, and requests at most
SAMPLE_TOKENS = 384
SAMPLE_MAX = 8


def load_limits(root: Path, cell: str) -> dict:
    return json.loads((root / "bench" / "limits" / f"{cell}.json")
                      .read_text())


def pick_sample(engine, records, seed: int) -> list:
    """``(prompt, served tokens)`` of requests: those finished by the close,
    the longest first, then the others in an order drawn from the seed;
    after them those still running, the one served the most first, then in
    an order drawn from the seed; until the sample holds ``SAMPLE_TOKENS``
    served tokens or ``SAMPLE_MAX`` requests."""
    from bench.model import seed_key

    rng = np.random.default_rng(seed_key(seed) ^ 0xC0FFEE)
    groups = []
    for done in (True, False):
        g = [(r.item.prompt, list(engine.results[r.uid].tokens))
             for r in records if engine.results[r.uid].tokens
             and bool(engine.results[r.uid].finish_reason) == done]
        if g:
            first = max(range(len(g)), key=lambda i: len(g[i][1]))
            rest = [s for i, s in enumerate(g) if i != first]
            groups.append([g[first]] + [rest[int(i)] for i in
                                        rng.permutation(len(rest))])
    sample, total = [], 0
    for s in (s for g in groups for s in g):
        if total >= SAMPLE_TOKENS or len(sample) >= SAMPLE_MAX:
            break
        sample.append(s)
        total += len(s[1])
    return sample


def served_gaps(a, w: dict, c: dict, prompt: np.ndarray, served: list[int]
                ) -> np.ndarray:
    """Reference gap of every served token of one request; ``a`` is the
    architecture's module (:func:`bench.arch.load`)."""
    from bench import reference

    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    pos = np.arange(len(prompt) - 1, len(seq), dtype=np.int32)
    gaps, _ = reference.logit_gaps(a, w, c, seq, pos, served)
    return gaps


def compare(a, w: dict, c: dict,
            samples: list[tuple[np.ndarray, list[int]]]) -> dict:
    """Readings over the sample: the widest gap, the mean gap over every
    compared token, and how many tokens."""
    return summarize([served_gaps(a, w, c, p, s) for p, s in samples])


def summarize(gaps: list[np.ndarray]) -> dict:
    """The numbers compared, from the gaps of each request of a sample."""
    if not gaps:
        return {"widest_gap": float("inf"), "mean_gap": float("inf"),
                "tokens_compared": 0, "requests_compared": 0}
    g = np.concatenate(gaps)
    return {"widest_gap": float(g.max()), "mean_gap": float(g.mean()),
            "tokens_compared": int(len(g)), "requests_compared": len(gaps)}


def verdict(readings: dict, limits: dict, *, failed: int, finished: int
            ) -> tuple[bool, dict]:
    """``correct`` and every number compared beside its limit: each gap the
    limits file bounds, at least half of ``SAMPLE_TOKENS`` compared, no
    request refused or cut, and at least one finished in the window."""
    checks = {k: {"value": readings[k], "limit": v, "ok": readings[k] <= v}
              for k, v in limits.items() if k in readings}
    checks["tokens_compared"] = {
        "value": readings["tokens_compared"], "limit": SAMPLE_TOKENS // 2,
        "ok": readings["tokens_compared"] >= SAMPLE_TOKENS // 2}
    checks["failed_requests"] = {"value": failed, "limit": 0,
                                 "ok": failed == 0}
    checks["finished_in_window"] = {"value": finished, "limit": 1,
                                    "ok": finished >= 1}
    return all(v["ok"] for v in checks.values()), checks
