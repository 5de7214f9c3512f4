"""The plain reference: the published decoder's forward pass in float32.

Embedding lookup, then per layer RMSNorm, q/k/v projections (Qwen3: RMSNorm
of each q and k head), rotary positions (half-split rotation, as the
published ``rotate_half``), causal grouped-query attention, the output
projection, RMSNorm and the SwiGLU MLP, each with a residual; a final
RMSNorm and the vocabulary projection (the embedding, where tied). It reads
the benchmark's weights (``bench/model.py`` layout) and imports nothing of
the program. Matmuls run at ``highest`` precision, so float32 means float32
on a TPU too.

It runs one layer at a time, with queries in blocks, so that a 15-layer 7B
model and several-thousand-token sequences fit beside the weights.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
#: sequences are padded to a multiple of this, so a few shapes compile
SEQ_BUCKET = 2048
LOGIT_BLOCK = 256


def _rms(x, w, eps):
    return (x * (1.0 / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps))
            * w.astype(jnp.float32))


def _rope(x, theta):
    """x: (S, heads, hd), position = row index."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) * 2 / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _layer(x, lw, dims):
    H, K, hd, eps, theta, qk_norm = dims
    S = x.shape[0]
    f32 = jnp.float32
    h = _rms(x, lw["attn_norm"], eps)
    q = (h @ lw["wq"].astype(f32)).reshape(S, H, hd)
    k = (h @ lw["wk"].astype(f32)).reshape(S, K, hd)
    v = (h @ lw["wv"].astype(f32)).reshape(S, K, hd)
    if qk_norm:
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    G = H // K
    k = jnp.repeat(k, G, axis=1)                      # head h reads h // G
    v = jnp.repeat(v, G, axis=1)
    scale = 1.0 / np.sqrt(hd)
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    att = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * hd)
    x = x + att @ lw["wo"].astype(f32)
    h = _rms(x, lw["mlp_norm"], eps)
    g = h @ lw["w_gate"].astype(f32)
    u = h @ lw["w_up"].astype(f32)
    return x + (jax.nn.silu(g) * u) @ lw["w_down"].astype(f32)


@functools.partial(jax.jit, static_argnums=(3,))
def _head_gap(x, norm, head, eps, tok):
    """For rows x (n, d) and tokens tok (n,): the reference's best logit
    minus its logit of tok, and its own argmax."""
    lg = _rms(x, norm, eps) @ head.astype(jnp.float32)
    best = lg.max(-1)
    return best - jnp.take_along_axis(lg, tok[:, None], 1)[:, 0], lg.argmax(-1)


def int8(x, axis: int):
    """``x`` rounded to int8 with one absmax scale per slice along ``axis``
    (the stored values of an int8 weight), as bf16: the precision below
    bf16, for a control."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.abs(x).max(axis=axis, keepdims=True), 1e-8) / 127
    return (jnp.clip(jnp.round(x / s), -127, 127) * s).astype(jnp.bfloat16)


def hidden(w: dict, c: dict, tokens: np.ndarray, quant: bool = False):
    """Final hidden states (S_pad, d), float32, of one sequence; with
    ``quant`` every weight matrix is first rounded to int8 (per output
    column; embedding rows per row), one layer at a time."""

    S = len(tokens)
    S_pad = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    tok = np.zeros(S_pad, np.int32)
    tok[:S] = tokens
    dims = (c["num_attention_heads"], c["num_key_value_heads"],
            c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            float(c["rms_norm_eps"]), float(c["rope_theta"]),
            c["model_type"] == "qwen3")
    layer_keys = [k for k in w if w[k].ndim >= 2 and k not in ("embed",
                                                                 "lm_head")]
    with jax.default_matmul_precision("highest"):
        x = w["embed"][jnp.asarray(tok)]
        x = (int8(x, -1) if quant else x).astype(jnp.float32)
        for i in range(c["num_hidden_layers"]):
            lw = {k: w[k][i] for k in layer_keys}
            if quant:
                lw = {k: int8(v, 0) if v.ndim == 2 else v
                      for k, v in lw.items()}
            x = _layer(x, lw, dims)
    return x


def logit_gaps(w: dict, c: dict, tokens: np.ndarray, positions: np.ndarray,
               chosen: np.ndarray, quant: bool = False
               ) -> tuple[np.ndarray, np.ndarray]:
    """For each position p (logits read after token p): the reference's
    best logit minus its logit of ``chosen`` (``(n,)``, or ``(k, n)`` for k
    candidates at once), and the reference's argmax (``quant``: of the
    reference with int8-rounded weights)."""
    x = hidden(w, c, tokens, quant)
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
                g, a = _head_gap(rows, w["final_norm"], head, eps,
                                 jnp.asarray(tok))
                gaps[i].append(np.asarray(g)[:n])
            best.append(np.asarray(a)[:n])
    out = np.stack([np.concatenate(g) for g in gaps])
    return (out[0] if out.shape[0] == 1 else out), np.concatenate(best)
