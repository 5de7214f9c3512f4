"""DeepSeek-V2 (``model_type`` ``deepseek_v2``): multi-head latent attention
(MLA) with YaRN positions in every layer; the first
``first_k_dense_replace`` layers (one, here) have a dense SwiGLU MLP, every
later one routed experts (softmax scores, top-k, ``norm_topk_prob``,
``routed_scaling_factor``) beside shared experts; an untied output head.

Weights are in a plain layout of the benchmark's own, as published
(``wq`` is ``(layers, d_model, heads * qk_head_dim)``, a norm is its
weight), drawn by :func:`bench.model.make_weights`: the dense layer's under
``l0.*`` unstacked, the expert layers' stacked over those layers, experts
``(layers, experts, d_model, width)``. :func:`to_program` hands the same
arrays to the program. Only what the program's MLA and expert layers can
express is accepted: no query compression, one leading dense layer, an
expert layer in every later layer, greedy top-k over softmax scores.

The reference (:func:`hidden`) is the published forward pass of
``modeling_deepseek.py`` in float32 and imports nothing of the program:
embedding; per layer RMSNorm, ``q = h Wq`` split per head into ``q_nope``
and ``q_pe``, ``[c, k_pe] = h W_kv_a``, ``c_n = RMSNorm(c)``, ``[k_nope,
v] = c_n W_kv_b`` per head, RoPE on ``q_pe`` and the shared ``k_pe`` in the
published interleaved-pair layout with YaRN's frequencies and cos/sin
factor, causal attention of ``[q_nope, q_pe]`` against ``[k_nope, k_pe]``
with softmax scale ``qk_head_dim^-0.5 · mscale(mscale_all_dim)^2``, the
output projection; then RMSNorm and the dense MLP or the expert layer
(the router's softmax over all experts in float32, the top-k weights,
each routed expert's SwiGLU, plus the shared experts' SwiGLU), each with a
residual. K and V are decompressed per head, as published (the program
decodes absorbed). Matmuls run at ``highest`` precision. It runs one
layer at a time, with queries in blocks, and within an expert layer one
expert at a time over every token, weighted by that expert's top-k weight
(zero where the token did not pick it), so that a 16k-token sequence fits
beside the weights.

(No ``from __future__ import annotations`` here: :func:`bench.arch.load`
may load this file by path, outside ``sys.modules``, where a dataclass
cannot resolve string annotations.)
"""
import dataclasses
import functools
import math
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
    Counts follow the model's published equations: the routed experts a
    token picks (not all of them) and the shared ones; attention with K and
    V decompressed per head. ``mla_attention`` is the least work of the
    absorbed decode that the program runs."""
    n_layers: int
    d_model: int
    n_heads: int
    vocab: int
    d_ff: int                 # the dense layers' MLP width
    n_dense: int              # leading dense layers
    n_experts: int
    top_k: int
    d_expert: int
    d_shared: int             # the shared experts' width, together
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_head: int
    tied: bool = False
    dtype_bytes: int = 2

    @property
    def n_moe(self) -> int:
        return self.n_layers - self.n_dense

    # ---- parameters -------------------------------------------------------
    @property
    def attn_params(self) -> int:
        """q, kv_a, kv_b and o projections of one layer."""
        d, H = self.d_model, self.n_heads
        r, dr = self.kv_lora_rank, self.qk_rope
        return (d * H * (self.qk_nope + dr) + d * (r + dr)
                + r * H * (self.qk_nope + self.v_head) + H * self.v_head * d)

    @property
    def dense_mlp_params(self) -> int:
        return 3 * self.d_model * self.d_ff

    @property
    def moe_params(self) -> int:
        """Every expert, the shared ones and the router, of one layer."""
        d = self.d_model
        return (3 * d * self.d_expert * self.n_experts + 3 * d * self.d_shared
                + d * self.n_experts)

    @property
    def active_moe_params(self) -> int:
        """What one token multiplies in an expert layer: its top-k routed
        experts, the shared ones and the router."""
        d = self.d_model
        return (3 * d * self.d_expert * self.top_k + 3 * d * self.d_shared
                + d * self.n_experts)

    @property
    def params(self) -> int:
        """Every parameter, norms included; a tied head counts once."""
        d, L = self.d_model, self.n_layers
        norms = L * (2 * d + self.kv_lora_rank) + d
        head = 0 if self.tied else d * self.vocab
        return (L * self.attn_params + self.n_dense * self.dense_mlp_params
                + self.n_moe * self.moe_params + self.vocab * d + head
                + norms)

    @property
    def weight_bytes(self) -> int:
        return self.params * self.dtype_bytes

    # ---- the latent cache ---------------------------------------------------
    @property
    def kv_row_bytes(self) -> int:
        """One cached latent row: c_n and the shared RoPE key."""
        return (self.kv_lora_rank + self.qk_rope) * self.dtype_bytes

    @property
    def kv_token_bytes(self) -> int:
        return self.n_layers * self.kv_row_bytes

    # ---- operations -------------------------------------------------------
    def context_flops(self, rows: float) -> float:
        """QK^T (over the query/key head dim) and PV (over the value head
        dim) of ``rows`` context rows, one query each, over all layers."""
        H = self.n_heads
        per = 2.0 * H * (self.qk_nope + self.qk_rope + self.v_head)
        return per * self.n_layers * rows

    def token_flops(self, context: int, logits: bool) -> float:
        """Model FLOPs of one token that attends to ``context`` positions:
        every projection, the dense MLPs, the routed experts it picks, the
        shared experts and the router, the attention over the context, and
        the vocabulary projection where its logits are used."""
        f = 2.0 * (self.n_layers * self.attn_params
                   + self.n_dense * self.dense_mlp_params
                   + self.n_moe * self.active_moe_params)
        f += self.context_flops(context)
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
        return (n * self.token_flops(0, logits=False)
                + self.context_flops(ctx) + 2.0 * self.d_model * self.vocab)

    def mla_attention(self, rows: int) -> tuple[float, float]:
        """(FLOPs, least bytes) of absorbed decode attention over ``rows``
        latent rows in one layer: each row read once for all heads, every
        head scoring it over the latent and the RoPE key and summing its
        latent (``H · (r + dr + r) · 2`` FLOPs)."""
        r = self.kv_lora_rank
        flops = 2.0 * self.n_heads * (r + self.qk_rope + r) * rows
        return flops, float(rows * self.kv_row_bytes)


def shape(c: dict) -> Shape:
    n_shared = int(c["n_shared_experts"] or 0)
    return Shape(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                 n_heads=c["num_attention_heads"], vocab=c["vocab_size"],
                 d_ff=c["intermediate_size"],
                 n_dense=int(c["first_k_dense_replace"]),
                 n_experts=c["n_routed_experts"],
                 top_k=c["num_experts_per_tok"],
                 d_expert=c["moe_intermediate_size"],
                 d_shared=n_shared * c["moe_intermediate_size"],
                 kv_lora_rank=c["kv_lora_rank"],
                 qk_nope=c["qk_nope_head_dim"], qk_rope=c["qk_rope_head_dim"],
                 v_head=c["v_head_dim"],
                 tied=bool(c["tie_word_embeddings"]))


def _check(c: dict) -> None:
    """Refuse what the program's layers cannot express."""
    want = {"first_k_dense_replace": 1, "moe_layer_freq": 1,
            "scoring_func": "softmax", "topk_method": "greedy",
            "q_lora_rank": None, "hidden_act": "silu"}
    for k, v in want.items():
        if c.get(k) != v:
            raise ValueError(f"deepseek_v2: {k}={c.get(k)!r}; the program "
                             f"serves {v!r}")
    if float(c["routed_scaling_factor"]) != 1.0:
        raise ValueError("deepseek_v2: routed_scaling_factor must be 1")
    if (c.get("rope_scaling") or {}).get("type") != "yarn":
        raise ValueError("deepseek_v2: rope_scaling must be YaRN")


# ---- the program ----------------------------------------------------------
def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``: the registry
    entry named by ``program_arch`` with every size from the published keys,
    bf16 weights, the paged engine and the kernels chosen by platform."""
    from repro.configs import (LayerSpec, MLAConfig, RopeScaling, get_arch)

    _check(c)
    s = shape(c)
    eng = c["engine"]
    rs = c["rope_scaling"]
    base = get_arch(c["program_arch"])
    return dataclasses.replace(
        base, n_layers=s.n_layers, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=c["num_key_value_heads"], d_ff=s.d_ff, vocab_size=s.vocab,
        tie_embeddings=s.tied,
        prefix=(LayerSpec("mla", "dense"),) * s.n_dense,
        pattern=(LayerSpec("mla", "moe"),),
        moe=dataclasses.replace(
            base.moe, n_experts=s.n_experts, top_k=s.top_k,
            n_shared=int(c["n_shared_experts"] or 0), d_expert=s.d_expert,
            d_shared=0, renorm_topk=bool(c["norm_topk_prob"]),
            shared_gate=False),
        mla=MLAConfig(kv_lora_rank=s.kv_lora_rank, q_lora_rank=0,
                      qk_nope_head_dim=s.qk_nope,
                      qk_rope_head_dim=s.qk_rope, v_head_dim=s.v_head),
        rope_theta=float(c["rope_theta"]),
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position=int(rs["original_max_position_embeddings"]),
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])),
        norm_eps=float(c["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=c["torch_dtype"],
        paged_kv=True, page_size=eng["page_size"],
        prefill_chunk=eng["prefill_chunk"],
        prefix_cache=bool(eng["prefix_cache"]),
        kernel_backend=eng["kernel_backend"])


_ATTN = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm")


def weight_shapes(c: dict) -> dict[str, tuple[int, ...]]:
    s = shape(c)
    _check(c)
    d, H, L = s.d_model, s.n_heads, s.n_moe
    r, dr = s.kv_lora_rank, s.qk_rope
    attn = {"attn_norm": (d,), "wq": (d, H * (s.qk_nope + dr)),
            "wkv_a": (d, r + dr), "kv_norm": (r,),
            "wkv_b": (r, H * (s.qk_nope + s.v_head)),
            "wo": (H * s.v_head, d), "mlp_norm": (d,)}
    shapes = {"embed": (s.vocab, d), "final_norm": (d,)}
    shapes.update({f"l0.{k}": v for k, v in attn.items()})
    shapes.update({"l0.w_gate": (d, s.d_ff), "l0.w_up": (d, s.d_ff),
                   "l0.w_down": (s.d_ff, d)})
    shapes.update({k: (L,) + v for k, v in attn.items()})
    E, f, fs = s.n_experts, s.d_expert, s.d_shared
    shapes.update({"router": (L, d, E),
                   "w_gate": (L, E, d, f), "w_up": (L, E, d, f),
                   "w_down": (L, E, f, d),
                   "shared_gate": (L, d, fs), "shared_up": (L, d, fs),
                   "shared_down": (L, fs, d)})
    if not s.tied:
        shapes["lm_head"] = (d, s.vocab)
    return shapes


def to_program(w: dict, c: dict):
    """The program's parameter tree over the same arrays: its norms store
    ``weight - 1`` (exact for the ones drawn here), the dense layer is its
    unrolled prefix, the expert layers one scanned period."""
    def norm(x):
        return {"scale": (x - 1).astype(x.dtype)}

    def attn(g):
        return {"q_proj": {"kernel": g("wq")},
                "kv_a_proj": {"kernel": g("wkv_a")},
                "kv_norm": norm(g("kv_norm")),
                "kv_b_proj": {"kernel": g("wkv_b")},
                "o_proj": {"kernel": g("wo")}}

    def l0(k):
        return w[f"l0.{k}"]

    def moe(k):
        return w[k]

    dense = {"pre_norm": norm(l0("attn_norm")), "attn": attn(l0),
             "mlp_norm": norm(l0("mlp_norm")),
             "mlp": {"gate": {"kernel": l0("w_gate")},
                     "up": {"kernel": l0("w_up")},
                     "down": {"kernel": l0("w_down")}}}
    block = {"pre_norm": norm(w["attn_norm"]), "attn": attn(moe),
             "mlp_norm": norm(w["mlp_norm"]),
             "moe": {"router": {"kernel": w["router"]},
                     "experts": {"gate": w["w_gate"], "up": w["w_up"],
                                 "down": w["w_down"]},
                     "shared": {"gate": {"kernel": w["shared_gate"]},
                                "up": {"kernel": w["shared_up"]},
                                "down": {"kernel": w["shared_down"]}}}}
    p = {"embed": {"table": w["embed"]}, "final_norm": norm(w["final_norm"]),
         "prefix": [dense], "blocks": [block]}
    if "lm_head" in w:
        p["lm_head"] = {"kernel": w["lm_head"]}
    return p


# ---- the reference --------------------------------------------------------
def _yarn_mscale(scale: float, mscale: float) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_cos_sin(c: dict, S: int):
    """cos, sin ``(S, qk_rope_head_dim)`` of the published
    ``DeepseekV2YarnRotaryEmbedding`` at positions ``0..S-1``."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    factor = float(rs["factor"])
    orig = int(rs["original_max_position_embeddings"])

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(corr_dim(float(rs["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    t = jnp.arange(S, dtype=jnp.float32)
    freqs = t[:, None] * jnp.asarray(inv_freq, jnp.float32)[None]
    m = (_yarn_mscale(factor, float(rs["mscale"]))
         / _yarn_mscale(factor, float(rs["mscale_all_dim"])))
    emb = jnp.concatenate([freqs, freqs], -1)
    return jnp.cos(emb) * m, jnp.sin(emb) * m


def softmax_scale(c: dict) -> float:
    s = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    rs = c["rope_scaling"]
    if rs and rs.get("mscale_all_dim"):
        m = _yarn_mscale(float(rs["factor"]), float(rs["mscale_all_dim"]))
        s = s * m * m
    return s


def _rope(x, cos, sin):
    """Published ``apply_rotary_pos_emb``: x (S, n, d) viewed as d/2 pairs,
    pairs de-interleaved, then ``x cos + rotate_half(x) sin``."""
    S, n, d = x.shape
    x = x.reshape(S, n, d // 2, 2).transpose(0, 1, 3, 2).reshape(S, n, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos[:, None] + rot * sin[:, None]


def _layer(w: dict, i, quant: bool) -> dict:
    """Layer ``i`` of the stacked weights ``w`` (all of ``w`` where ``i`` is
    None), every matrix but the experts rounded to int8 where ``quant``
    (per output column)."""
    lw = {k: (v if i is None else v[i]) for k, v in w.items()}
    if quant:
        lw = {k: int8(v, 0) if v.ndim == 2 else v for k, v in lw.items()}
    return lw


@functools.partial(jax.jit, static_argnums=(5, 6))
def _attention(x, w, i, cos, sin, dims, quant):
    """x + the latent-attention block of one layer, decompressed."""
    H, dn, dr, dv, r, eps, scale = dims
    lw = _layer(w, i, quant)
    S = x.shape[0]
    f32 = jnp.float32
    h = rms(x, lw["attn_norm"], eps)
    q = (h @ lw["wq"].astype(f32)).reshape(S, H, dn + dr)
    kv_a = h @ lw["wkv_a"].astype(f32)
    c_n = rms(kv_a[:, :r], lw["kv_norm"], eps)
    kv = (c_n @ lw["wkv_b"].astype(f32)).reshape(S, H, dn + dv)
    q_pe = _rope(q[..., dn:], cos, sin)
    k_pe = _rope(kv_a[:, None, r:], cos, sin)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(k_pe, (S, H, dr))],
                        -1)
    v = kv[..., dn:]
    kpos = jnp.arange(S)

    def block(b):
        qb = jax.lax.dynamic_slice_in_dim(q, b * Q_BLOCK, Q_BLOCK, 0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    att = jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * dv)
    return x + att @ lw["wo"].astype(f32)


def _swiglu(h, w_gate, w_up, w_down):
    f32 = jnp.float32
    return ((jax.nn.silu(h @ w_gate.astype(f32)) * (h @ w_up.astype(f32)))
            @ w_down.astype(f32))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _dense_mlp(x, w, eps, quant):
    lw = _layer(w, None, quant)
    h = rms(x, lw["mlp_norm"], eps)
    return x + _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"])


@functools.partial(jax.jit, static_argnums=(3, 4))
def _moe(x, w, i, dims, quant):
    """x + the expert layer of layer ``i``: softmax over every expert, the
    top-k weights (renormalised where ``norm_topk_prob``, times the scaling
    factor), one expert at a time over all tokens, plus the shared
    experts."""
    k, eps, renorm, scaling = dims
    f32 = jnp.float32
    lw = _layer({n: v for n, v in w.items()
                 if n not in ("w_gate", "w_up", "w_down")}, i, quant)
    h = rms(x, lw["mlp_norm"], eps)
    probs = jax.nn.softmax(h @ lw["router"].astype(f32), axis=-1)  # (S, E)
    top, idx = jax.lax.top_k(probs, k)
    if renorm:
        top = top / top.sum(-1, keepdims=True)
    top = top * scaling

    def expert(acc, e):
        wt = jnp.where(idx == e, top, 0.0).sum(-1)               # (S,)
        ws = [w[n][i, e] for n in ("w_gate", "w_up", "w_down")]
        if quant:
            ws = [int8(m, 0) for m in ws]
        return acc + wt[:, None] * _swiglu(h, *ws), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                             jnp.arange(probs.shape[-1]))
    shared = _swiglu(h, lw["shared_gate"], lw["shared_up"],
                     lw["shared_down"])
    return x + routed + shared


def hidden(w: dict, c: dict, tokens: np.ndarray, quant: bool = False):
    """Final hidden states (S_pad, d), float32, of one sequence; with
    ``quant`` every weight matrix is first rounded to int8 (per output
    column, each expert's on its own; embedding rows per row)."""
    _check(c)
    S = len(tokens)
    S_pad = -(-S // SEQ_BUCKET) * SEQ_BUCKET
    tok = np.zeros(S_pad, np.int32)
    tok[:S] = tokens
    eps = float(c["rms_norm_eps"])
    attn_dims = (c["num_attention_heads"], c["qk_nope_head_dim"],
                 c["qk_rope_head_dim"], c["v_head_dim"], c["kv_lora_rank"],
                 eps, softmax_scale(c))
    moe_dims = (c["num_experts_per_tok"], eps, bool(c["norm_topk_prob"]),
                float(c["routed_scaling_factor"]))
    l0 = {k[3:]: v for k, v in w.items() if k.startswith("l0.")}
    stacked = {k: v for k, v in w.items() if "." not in k
               and k not in ("embed", "lm_head", "final_norm")}
    with jax.default_matmul_precision("highest"):
        cos, sin = yarn_cos_sin(c, S_pad)
        x = w["embed"][jnp.asarray(tok)]
        x = (int8(x, -1) if quant else x).astype(jnp.float32)
        x = _attention(x, l0, None, cos, sin, attn_dims, quant)
        x = _dense_mlp(x, l0, eps, quant)
        for i in range(c["num_hidden_layers"] - 1):
            li = jnp.asarray(i, jnp.int32)
            x = _attention(x, stacked, li, cos, sin, attn_dims, quant)
            x = _moe(x, stacked, li, moe_dims, quant)
    return x
