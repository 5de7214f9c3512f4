"""One general generator for every traffic mix.

A mix is a JSON file of parameters under ``bench/traffic/``:

``loop``
    ``"open"``: requests fall due on a Poisson schedule at ``rate_per_s``,
    whatever the system does. ``"closed"``: ``clients`` callers each send
    their next request when the last one finishes; ``"pool"`` means as many
    as the KV pool holds at the largest reservation.
``prompt``, ``output``
    Length distributions: ``{"dist": "lognormal", "median", "sigma"}`` or
    ``{"dist": "uniform"}``, each clipped to ``[min, max]``.
``shared_prefix``
    ``null``, or ``{"count", "tokens", "zipf_s"}``: that many system prompts
    of that length, picked with Zipf popularity; ``prompt`` is then the
    fresh suffix after it. The system prompts are pre-filled in set-up.

The schedule is the same for every seed: lengths, gaps and system-prompt
picks are drawn at evenly spaced quantiles of their distributions and put
in one fixed shuffled order. The seed draws the token ids (and, elsewhere,
the weights). A window sees a few dozen requests, so a seed that reordered
them would change the work: on the chat cell that moved tokens/s by 24%
between seeds, against under 2% between two runs of one seed.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from bench.model import seed_key

#: the one order every seed's schedule is shuffled into
SCHEDULE_SEED = 20251017


@dataclass
class Item:
    """One request of the mix, before it becomes a program ``Request``."""
    prompt: np.ndarray              # (S,) int32, system prompt included
    max_new: int
    prefix: int = -1                # index of its system prompt, -1: none
    due: float = 0.0                # seconds after the window opens (open)


@dataclass
class Mix:
    params: dict
    items: list[Item]
    prefixes: list[np.ndarray] = field(default_factory=list)

    @property
    def open_loop(self) -> bool:
        return self.params["loop"] == "open"


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``, clipped."""
    q = _quantiles(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        v = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def mean_length(spec: dict, n: int = 4096) -> float:
    return float(lengths(spec, n).mean())


def zipf_picks(count: int, s: float, n: int) -> np.ndarray:
    """``n`` picks among ``count`` items in Zipf(s) proportions, rounded so
    the counts sum to ``n`` (largest remainders first)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    k = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - k), kind="stable")
    k[order[:n - int(k.sum())]] += 1
    return np.repeat(np.arange(count), k)


def n_requests(params: dict, seconds: float, clients: int) -> int:
    """Requests to draw: every one due in an open-loop window, or for a
    closed loop enough that no client runs dry."""
    if params["loop"] == "open":
        return max(1, int(math.floor(float(params["rate_per_s"]) * seconds)))
    return max(4 * clients, 64 + clients * int(math.ceil(seconds)))


def generate(params: dict, seed: int, seconds: float, vocab: int,
             clients: int = 0) -> Mix:
    n = n_requests(params, seconds, clients)
    order = np.random.default_rng(SCHEDULE_SEED)
    rng = np.random.default_rng(seed_key(seed))
    p_len = order.permutation(lengths(params["prompt"], n))
    o_len = order.permutation(lengths(params["output"], n))
    sp = params.get("shared_prefix")
    prefixes: list[np.ndarray] = []
    picks = np.full(n, -1)
    if sp:
        prefixes = [rng.integers(0, vocab, int(sp["tokens"]), dtype=np.int32)
                    for _ in range(int(sp["count"]))]
        picks = order.permutation(zipf_picks(int(sp["count"]),
                                             float(sp["zipf_s"]), n))
    due = np.zeros(n)
    if params["loop"] == "open":
        # gaps at exponential quantiles, scaled to mean 1/rate exactly, so
        # all n requests fall due inside the window
        gaps = -np.log1p(-_quantiles(n))
        gaps = order.permutation(gaps / gaps.sum() * n) / float(
            params["rate_per_s"])
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    items = []
    for i in range(n):
        fresh = rng.integers(0, vocab, int(p_len[i]), dtype=np.int32)
        k = int(picks[i])
        prompt = fresh if k < 0 else np.concatenate([prefixes[k], fresh])
        items.append(Item(prompt=prompt, max_new=int(o_len[i]), prefix=k,
                          due=float(due[i])))
    return Mix(params=params, items=items, prefixes=prefixes)


def largest_reservation(params: dict) -> int:
    """Tokens the longest request holds: system prompt, prompt, output."""
    sp = params.get("shared_prefix") or {}
    return (int(sp.get("tokens", 0)) + int(params["prompt"]["max"])
            + int(params["output"]["max"]))


def mean_fresh_tokens(params: dict) -> float:
    """Mean tokens a request holds of its own: its fresh prompt and its
    output (a shared system prompt is held once, by the prefix cache)."""
    return mean_length(params["prompt"]) + mean_length(params["output"])


def shared_tokens(params: dict) -> int:
    sp = params.get("shared_prefix") or {}
    return int(sp.get("count", 0)) * int(sp.get("tokens", 0))
