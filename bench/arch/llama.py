"""The Llama decoder (``model_type`` ``llama``): grouped-query attention with
rotary positions and a SwiGLU MLP, RMSNorm before each, an embedding and an
output head that may be tied to it. :mod:`bench.arch.qwen3` is this decoder
with an RMSNorm over each query and key head.

Weights are in a plain layout of the benchmark's own (``wq`` is ``(layers,
d_model, heads * head_dim)``, a norm is its weight, as published), stacked
over the layers. :func:`program_config` maps the published keys onto the
program's ``ModelConfig``; nothing else of the registry entry named by
``program_arch`` is trusted for a size.

The reference (:func:`hidden`) is the published forward pass in float32:
embedding lookup, then per layer RMSNorm, q/k/v projections (with
``qk_norm``: RMSNorm of each q and k head), rotary positions (half-split
rotation, as the published ``rotate_half``), causal grouped-query
attention, the output projection, RMSNorm and the SwiGLU MLP, each with a
residual. It imports nothing of the program. Matmuls run at ``highest``
precision, so float32 means float32 on a TPU too. It runs one layer at a
time, with queries in blocks, so that a 15-layer 7B model and
several-thousand-token sequences fit beside the weights.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import int8, rms

Q_BLOCK = 512
#: sequences are padded to a multiple of this, so a few shapes compile
SEQ_BUCKET = 2048


# ---- sizes and counts -------------------------------------------------------
@dataclass(frozen=True)
class Shape:
    """The sizes the arithmetic needs, read from a configuration file.
    Counts follow the model's equations, not what the program happens to
    execute: a kernel that reads more than it needs, or computes rows that
    no request uses, shows as a lower share of its roofline."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qk_norm: bool = False
    dtype_bytes: int = 2

    # ---- parameters -------------------------------------------------------
    @property
    def attn_params(self) -> int:
        """q, k, v and o projections of one layer."""
        d, hd = self.d_model, self.head_dim
        return d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)

    @property
    def mlp_params(self) -> int:
        """gate, up and down projections of one layer."""
        return 3 * self.d_model * self.d_ff

    @property
    def layer_matmul_params(self) -> int:
        return self.attn_params + self.mlp_params

    @property
    def params(self) -> int:
        """Every parameter, norms included; a tied head counts once."""
        d, hd, L = self.d_model, self.head_dim, self.n_layers
        norms = L * (2 * d + (2 * hd if self.qk_norm else 0)) + d
        head = 0 if self.tied else d * self.vocab
        return L * self.layer_matmul_params + self.vocab * d + head + norms

    @property
    def weight_bytes(self) -> int:
        return self.params * self.dtype_bytes

    # ---- KV ---------------------------------------------------------------
    @property
    def kv_row_bytes(self) -> int:
        """K and V of one token in one layer."""
        return 2 * self.n_kv_heads * self.head_dim * self.dtype_bytes

    @property
    def kv_token_bytes(self) -> int:
        return self.n_layers * self.kv_row_bytes

    # ---- operations -------------------------------------------------------
    def context_flops(self, rows: float) -> float:
        """QK^T and PV of ``rows`` context rows (one query each), summed
        over all layers."""
        return 4.0 * self.n_layers * self.n_heads * self.head_dim * rows

    def token_flops(self, context: int, logits: bool) -> float:
        """Model FLOPs of one token that attends to ``context`` positions
        (itself included): every matmul of every layer, QK^T and PV over the
        context, and the vocabulary projection where its logits are used."""
        f = (2.0 * self.n_layers * self.layer_matmul_params
             + self.context_flops(context))
        if logits:
            f += 2.0 * self.d_model * self.vocab
        return f

    def prefill_flops(self, start: int, end: int) -> float:
        """Model FLOPs of prefilling positions ``start..end-1`` (position p
        attends to p + 1 positions); only the last position's logits are
        used."""
        n = end - start
        if n <= 0:
            return 0.0
        ctx = (start + 1 + end) * n / 2.0          # sum of p + 1
        return (2.0 * self.n_layers * self.layer_matmul_params * n
                + self.context_flops(ctx) + 2.0 * self.d_model * self.vocab)

    def gemm(self, m: int, k: int, n: int) -> tuple[float, float]:
        """(FLOPs, least HBM bytes) of one (m, k) x (k, n) bf16 matmul:
        each operand read once, the result written once."""
        b = self.dtype_bytes
        return 2.0 * m * k * n, float(b * (m * k + k * n + m * n))

    def paged_attention(self, kv_rows: int) -> tuple[float, float]:
        """(FLOPs, least bytes) of decode attention over ``kv_rows`` context
        rows in one layer: each row's K and V read once."""
        G = self.n_heads // self.n_kv_heads
        flops = 4.0 * kv_rows * self.n_kv_heads * G * self.head_dim
        return flops, float(kv_rows * self.kv_row_bytes)


def shape(c: dict, qk_norm: bool = False) -> Shape:
    return Shape(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                 n_heads=c["num_attention_heads"],
                 n_kv_heads=c["num_key_value_heads"],
                 head_dim=c.get("head_dim") or (c["hidden_size"]
                                                // c["num_attention_heads"]),
                 d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                 tied=bool(c["tie_word_embeddings"]), qk_norm=qk_norm)


# ---- the program ----------------------------------------------------------
def program_config(c: dict, qk_norm: bool = False):
    """The program's ``ModelConfig`` for configuration ``c``: the registry
    entry named by ``program_arch`` with every size from the published keys,
    bf16 weights, the paged engine and the kernels chosen by platform."""
    from repro.configs import get_arch

    s = shape(c)
    eng = c["engine"]
    base = get_arch(c["program_arch"])
    return dataclasses.replace(
        base, n_layers=s.n_layers, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab_size=s.vocab, tie_embeddings=s.tied, qk_norm=qk_norm,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=c["torch_dtype"],
        paged_kv=True, page_size=eng["page_size"],
        prefill_chunk=eng["prefill_chunk"],
        prefix_cache=bool(eng["prefix_cache"]),
        kernel_backend=eng["kernel_backend"])


def weight_shapes(c: dict, qk_norm: bool = False
                  ) -> dict[str, tuple[int, ...]]:
    s = shape(c)
    L, d, hd, f = s.n_layers, s.d_model, s.head_dim, s.d_ff
    shapes = {
        "embed": (s.vocab, d),
        "final_norm": (d,),
        "attn_norm": (L, d), "mlp_norm": (L, d),
        "wq": (L, d, s.n_heads * hd), "wk": (L, d, s.n_kv_heads * hd),
        "wv": (L, d, s.n_kv_heads * hd), "wo": (L, s.n_heads * hd, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    if qk_norm:
        shapes["q_norm"] = (L, hd)
        shapes["k_norm"] = (L, hd)
    if not s.tied:
        shapes["lm_head"] = (d, s.vocab)
    return shapes


def to_program(w: dict, c: dict):
    """The program's parameter tree over the same arrays: its norms store
    ``weight - 1`` (exact for the ones drawn here), its layers are one
    scanned period."""
    def norm(x):
        return {"scale": (x - 1).astype(x.dtype)}

    attn = {"q_proj": {"kernel": w["wq"]}, "k_proj": {"kernel": w["wk"]},
            "v_proj": {"kernel": w["wv"]}, "o_proj": {"kernel": w["wo"]}}
    if "q_norm" in w:
        attn["q_norm"] = norm(w["q_norm"])
        attn["k_norm"] = norm(w["k_norm"])
    block = {"pre_norm": norm(w["attn_norm"]), "attn": attn,
             "mlp_norm": norm(w["mlp_norm"]),
             "mlp": {"gate": {"kernel": w["w_gate"]},
                     "up": {"kernel": w["w_up"]},
                     "down": {"kernel": w["w_down"]}}}
    p = {"embed": {"table": w["embed"]}, "final_norm": norm(w["final_norm"]),
         "blocks": [block]}
    if "lm_head" in w:
        p["lm_head"] = {"kernel": w["lm_head"]}
    return p


# ---- the reference --------------------------------------------------------
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
    h = rms(x, lw["attn_norm"], eps)
    q = (h @ lw["wq"].astype(f32)).reshape(S, H, hd)
    k = (h @ lw["wk"].astype(f32)).reshape(S, K, hd)
    v = (h @ lw["wv"].astype(f32)).reshape(S, K, hd)
    if qk_norm:
        q = rms(q, lw["q_norm"], eps)
        k = rms(k, lw["k_norm"], eps)
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
    h = rms(x, lw["mlp_norm"], eps)
    g = h @ lw["w_gate"].astype(f32)
    u = h @ lw["w_up"].astype(f32)
    return x + (jax.nn.silu(g) * u) @ lw["w_down"].astype(f32)


def hidden(w: dict, c: dict, tokens: np.ndarray, quant: bool = False,
           qk_norm: bool = False):
    """Final hidden states (S_pad, d), float32, of one sequence; with
    ``quant`` every weight matrix is first rounded to int8 (per output
    column; embedding rows per row), one layer at a time."""

    S = len(tokens)
    S_pad = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    tok = np.zeros(S_pad, np.int32)
    tok[:S] = tokens
    dims = (c["num_attention_heads"], c["num_key_value_heads"],
            c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
            float(c["rms_norm_eps"]), float(c["rope_theta"]), qk_norm)
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
