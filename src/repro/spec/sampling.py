"""Shared sampling transforms for the serve engine and the speculative
acceptance rule.

The serve engine's fused sampler used to be temperature-only; the
speculative-decoding residual distribution ``norm(max(p - q, 0))`` is only
well-defined when the draft and the verifier agree on the *support* of
their per-step distributions, so top-k / top-p filtering has to live in one
place both can call. Everything here runs inside jitted graphs: shapes are
static, knobs ride in as traced per-row arrays (``top_k == 0`` and
``top_p >= 1`` disable filtering for that row, so one compiled graph serves
every knob combination).

Greedy rows (temperature <= 0) take the argmax in :func:`sample_tokens`;
a batch with no sampling row skips the filter and the categorical draw
(decided on the device, inside the one compiled graph), so filtering can
never perturb greedy parity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


#: PRNG purpose tags folded into per-slot stream keys so the same
#: (request, step) never reuses a key across the decode sampler, the
#: draft proposer, the acceptance rule, and fork derivation
P_SAMPLE, P_DRAFT, P_ACCEPT, P_FORK = 0, 1, 2, 3


def fold_keys(keys, ctrs, purpose):
    """Per-row stream keys: fold each slot's dispatch counter, then a
    purpose tag, into its base key. The resulting stream depends only on
    (request seed, step index, purpose) — never on which other requests
    share the batch or on admission order. Must run inside a jitted graph:
    an eager vmap re-traces on every call, which is milliseconds of host
    work per decode turn."""
    kk = jax.vmap(jax.random.fold_in)(keys, ctrs)
    return jax.vmap(jax.random.fold_in)(
        kk, jnp.full(ctrs.shape, purpose, jnp.uint32))


def filter_logits(logits, top_k, top_p):
    """Mask ``logits`` outside the per-row top-k / top-p (nucleus) sets.

    logits: (B, V) float; top_k: (B,) int32 (0 = off); top_p: (B,) float32
    (>= 1 = off). The most probable token always survives (top-1 is kept
    even when a degenerate ``top_p ~ 0`` would otherwise empty the nucleus),
    so the filtered distribution is never all ``-inf``. Sort-based: O(V log
    V) per row, fine at serving vocab sizes and trivially jittable.
    """
    B, V = logits.shape
    neg = jnp.asarray(-1e30, logits.dtype)
    order = jnp.argsort(logits, axis=-1)[:, ::-1]          # descending
    ranked = jnp.take_along_axis(logits, order, axis=-1)
    rank = jnp.arange(V)[None, :]
    # top-k: keep ranks < k (k == 0 disables)
    keep = jnp.where(top_k[:, None] > 0, rank < top_k[:, None], True)
    # top-p: keep the smallest prefix whose probability mass reaches p.
    # Rank r survives when the mass *before* it is still < p (the token that
    # crosses the threshold is included, per the usual nucleus definition).
    probs = jax.nn.softmax(ranked.astype(jnp.float32), axis=-1)
    prior = jnp.cumsum(probs, axis=-1) - probs             # mass before rank
    keep &= jnp.where(top_p[:, None] < 1.0,
                      prior < top_p[:, None], True)
    keep = keep.at[:, 0].set(True)                         # top-1 always
    ranked = jnp.where(keep, ranked, neg)
    # undo the sort: scatter the masked values back to vocab order
    inv = jnp.argsort(order, axis=-1)
    return jnp.take_along_axis(ranked, inv, axis=-1)


def filtered_probs(logits, temps, top_k, top_p):
    """Per-row sampling distribution after temperature + top-k/top-p.

    logits: (B, V); temps: (B,). Greedy rows (temp <= 0) get a one-hot on
    the argmax — the distribution a temperature-0 sampler draws from — so
    the speculative acceptance rule covers both regimes with one formula.
    Returns (B, V) float32 probabilities.
    """
    t = jnp.where(temps <= 0, 1.0, temps)[:, None]
    f = filter_logits(logits.astype(jnp.float32) / t, top_k, top_p)
    probs = jax.nn.softmax(f, axis=-1)
    onehot = jax.nn.one_hot(jnp.argmax(logits, axis=-1), logits.shape[-1],
                            dtype=jnp.float32)
    return jnp.where((temps <= 0)[:, None], onehot, probs)


def sample_tokens(logits, temps, top_k, top_p, keys):
    """Fused per-row sampler: greedy where temp <= 0, filtered categorical
    otherwise. keys: (B, 2) uint32 — one legacy PRNG key per row, so
    concurrent requests draw from independent, order-independent streams.
    Returns (B,) int32.

    Work follows the rows' knobs, decided on the device: with no row at
    temp > 0 only the argmax runs; with no sampling row asking for top-k
    or top-p the tempered logits go straight to the draw
    (:func:`filter_logits` with both filters off is the identity). Every
    branch returns what the full formula would, bit for bit.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampling = temps > 0

    def draw():
        t = jnp.where(temps <= 0, 1.0, temps)[:, None]
        f = logits.astype(jnp.float32) / t
        filtered = sampling & ((top_k > 0) | (top_p < 1.0))
        f = jax.lax.cond(jnp.any(filtered),
                         lambda x: filter_logits(x, top_k, top_p),
                         lambda x: x, f)
        sampled = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(keys, f)
        return jnp.where(temps <= 0, greedy, sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.any(sampling), draw, lambda: greedy)
