"""The plain reference's generic part: the vocabulary head over the final
hidden states that each architecture's ``hidden`` (``bench/arch/``) gives,
and the gap by which a chosen token's logit lies below the best: a final
RMSNorm, then the vocabulary projection. The head is ``lm_head`` where the
weights have one, else the embedding (tied). Nothing here imports the
program. Matmuls run at ``highest`` precision, so float32 means float32 on
a TPU too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LOGIT_BLOCK = 256


def rms(x, w, eps):
    return (x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))
            * w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnums=(3,))
def _head_gap(x, norm, head, eps, tok):
    """For rows x (n, d) and tokens tok (n,): the reference's best logit
    minus its logit of tok, and its own argmax."""
    lg = rms(x, norm, eps) @ head.astype(jnp.float32)
    best = lg.max(-1)
    return best - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0], lg.argmax(-1)


def int8(x, axis: int):
    """``x`` rounded to int8 with one absmax scale per slice along ``axis``
    (the stored values of an int8 weight), as bf16: the precision below
    bf16, for a control."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.abs(x).max(axis=axis, keepdims=True), 1e-8) / 127
    return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(jnp.bfloat16)


def logit_gaps(a, w: dict, c: dict, tokens: np.ndarray,
               positions: np.ndarray, chosen: np.ndarray, quant: bool = False
               ) -> tuple[np.ndarray, np.ndarray]:
    """For each position p (logits read after token p): the reference's
    best logit minus its logit of ``chosen`` (``(n,)``, or ``(k, n)`` for k
    candidates at once), and the reference's argmax (``quant``: of the
    reference with int8-rounded weights). ``a`` is the architecture's
    module (:func:`bench.arch.load`)."""
    x = a.hidden(w, c, tokens, quant)
    head = w["lm_head"] if "lm_head" in w else w["embed"].T
    if quant:
        head = int8(head, 0) if "lm_head" in w else int8(w["embed"], -1).T
    eps = float(c["rms_norm_eps"])
    chosen = np.atleast_2d(np.asarray(chosen, np.int32))
    gaps = [[] for _ in chosen]
    best = []
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(positions), LOGIT_BLOCK):
            n = min(LOGIT_BLOCK, len(positions) - lo)
            pos = np.zeros(LOGIT_BLOCK, np.int32)
            pos[:n] = positions[lo:lo + n]
            rows = x[jnp.asarray(pos)]
            for i, ch in enumerate(chosen):
                tok = np.zeros(LOGIT_BLOCK, np.int32)
                tok[:n] = ch[lo:lo + n]
                g, top = _head_gap(rows, w["final_norm"], head, eps,
                                   jnp.asarray(tok))
                gaps[i].append(np.asarray(g)[:n])
            best.append(np.asarray(top)[:n])
    out = np.stack([np.concatenate(g) for g in gaps])
    return (out[0] if out.shape[0] == 1 else out), np.concatenate(best)
