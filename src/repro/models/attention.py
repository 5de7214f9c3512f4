"""Attention: GQA with RoPE, sliding windows, logit softcaps, qk-norm, and
multi-head latent attention (MLA, DeepSeek-V2).

Training/prefill uses a chunked FlashAttention-2-style online-softmax scan in
pure jnp (``flash_attention_xla``) — this is both the production XLA path for
the CPU dry-run and the numerical oracle for the Pallas kernel
(kernels/flash_attention.py). The paper itself leverages FlashAttention-2 for
its GPT-J inference evaluation (§II-C), so this layer is paper-faithful.

Decode uses a single-query scoring path against a (possibly length-sharded)
KV cache — the context-parallel cache is the framework's analogue of spreading
Occamy's HBM channels across Ramora's mesh edge routers.

MLA (``mla_*``) follows ``modeling_deepseek.py`` of DeepSeek-V2 without
query compression (``q_lora_rank`` 0, as DeepSeek-V2-Lite): ``q = x Wq``,
split per head into ``q_nope`` and ``q_pe``; ``[c, k_pe] = x W_kv_a``;
``c_n = RMSNorm(c)``; ``[k_nope, v] = c_n W_kv_b`` per head; RoPE (YaRN
where configured) on ``q_pe`` and the one ``k_pe`` all heads share;
softmax scale ``qk_head_dim^-0.5 · mscale(mscale_all_dim)^2``; ``o =
attn(q, k, v) W_o``.
Training, full prefill and chunked prefill decompress K/V per head (chunked
prefill from the cached latent rows ``[c_n, k_pe]``, ``models/cache.py``);
decode is *absorbed*: ``q_lat = q_nope W_UK_h`` scores ``q_lat · c_n + q_pe
· k_pe`` against the cached rows and the output is ``(Σ p c_n) W_UV_h``
then ``W_o``, so per-head keys and values are never built. RoPE is applied
in the published interleaved-pair layout: ``q_pe`` and ``k_pe`` are
de-interleaved (``view(d/2, 2).transpose``) and then rotated half-split
(``rotate_half``), and ``k_pe`` is cached in that layout. Departures from
the published code: absorbed decode computes the same function in another
order and in the compute dtype, so it matches the decompressed one to
rounding; the cached row is zero-padded to whole 128-lane tiles.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import dispatch as kdispatch
from repro.models.layers import (Params, apply_norm, dense_init, norm_init,
                                 rope, softcap, yarn_mscale)

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)

#: rows of the slot's latent cache one step of chunked prefill decompresses
#: and scores at a time
EXTEND_KEY_BLOCK = 2048


# --------------------------------------------------------------------------
# params
# --------------------------------------------------------------------------
def attention_init(rng, cfg: ModelConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "q_proj": {"kernel": dense_init(ks[0], d, cfg.n_heads * hd, dtype)},
        "k_proj": {"kernel": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype)},
        "v_proj": {"kernel": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype)},
        "o_proj": {"kernel": dense_init(ks[3], cfg.n_heads * hd, d, dtype)},
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, "rmsnorm", dtype)
        p["k_norm"] = norm_init(hd, "rmsnorm", dtype)
    return p


def _scale(cfg: ModelConfig) -> float:
    s = cfg.attn_scale if cfg.attn_scale else cfg.resolved_head_dim
    return 1.0 / math.sqrt(s)


# --------------------------------------------------------------------------
# core chunked flash (online softmax) — jnp
# --------------------------------------------------------------------------
def flash_attention_xla(q, k, v, *, causal: bool, window: int, cap: float,
                        scale: float, q_chunk: int, kv_chunk: int,
                        q_offset=0, kv_lens=None, qc_constraint=None):
    """q: (B, Sq, K, G, D); k, v: (B, Skv, K, D). Returns (B, Sq, K, G, D).

    Online-softmax two-level scan (FlashAttention-2 schedule): outer over query
    chunks, inner over KV chunks with running (max, sum, acc) carried in fp32.
    ``window > 0`` masks to a sliding window; ``kv_lens`` (B,) masks ragged KV.
    ``q_offset`` is the absolute position of q[0] (decode/chunked prefill).
    """
    B, Sq, K, G, D = q.shape
    Skv = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    n_q = -(-Sq // q_chunk)
    n_kv = -(-Skv // kv_chunk)
    pad_q = n_q * q_chunk - Sq
    pad_kv = n_kv * kv_chunk - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    kv_valid = Skv if kv_lens is None else kv_lens  # scalar or (B,)

    qs = q.reshape(B, n_q, q_chunk, K, G, D).transpose(1, 0, 3, 4, 2, 5)
    ks = k.reshape(B, n_kv, kv_chunk, K, D).transpose(1, 0, 3, 2, 4)
    vs = v.reshape(B, n_kv, kv_chunk, K, D).transpose(1, 0, 3, 2, 4)

    def q_body(_, qi_qc):
        qi, qc = qi_qc  # qc: (B, K, G, q_chunk, D)
        if qc_constraint is not None:
            qc = qc_constraint(qc)  # context-parallel: shard the q-chunk dim
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_body(carry, ki_kc):
            m, l, acc = carry
            ki, kc, vc = ki_kc  # (B, K, kv_chunk, D)
            kv_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bkgqd,bkcd->bkgqc", qc, kc,
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, cap)
            mask = jnp.ones((q_chunk, kv_chunk), bool)
            if causal:
                mask &= q_pos[:, None] >= kv_pos[None, :]
            if window:
                mask &= q_pos[:, None] - kv_pos[None, :] < window
            valid = kv_pos < (kv_valid if jnp.ndim(kv_valid) == 0
                              else kv_valid[:, None, None, None, None])
            if jnp.ndim(kv_valid) == 0:
                mask &= valid[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            else:
                s = jnp.where(mask[None, None, None] & valid, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, K, G, q_chunk, D), jnp.float32)
        # checkpoint: recompute s/p per KV chunk in backward instead of saving
        # the (q_chunk, kv_chunk) probability tiles — the FlashAttention trade.
        body = (jax.checkpoint(kv_body, prevent_cse=False)
                if n_kv > 1 else kv_body)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0), (jnp.arange(n_kv), ks, vs))
        out = acc / jnp.maximum(l[..., None], 1e-37)
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(q_body, None, (jnp.arange(n_q), qs))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(B, n_q * q_chunk, K, G, D)
    return out[:, :Sq]


# --------------------------------------------------------------------------
# layer-level apply (projections + rope + attention)
# --------------------------------------------------------------------------
def _project_qkv(p: Params, cfg: ModelConfig, x, xkv, positions_q, positions_kv,
                 compute_dtype):
    B, Sq, _ = x.shape
    Skv = xkv.shape[1]
    hd, H, K = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // K
    xc = x.astype(compute_dtype)
    xkvc = xkv.astype(compute_dtype)
    q = (xc @ p["q_proj"]["kernel"].astype(compute_dtype)).reshape(B, Sq, K, G, hd)
    k = (xkvc @ p["k_proj"]["kernel"].astype(compute_dtype)).reshape(B, Skv, K, hd)
    v = (xkvc @ p["v_proj"]["kernel"].astype(compute_dtype)).reshape(B, Skv, K, hd)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    if cfg.use_rope and positions_q is not None:
        qf = q.reshape(B, Sq, K * G, hd)
        qf = rope(qf, positions_q, cfg.rope_theta, cfg.rope_scaling)
        q = qf.reshape(B, Sq, K, G, hd)
        k = rope(k, positions_kv, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def attention_forward(p: Params, cfg: ModelConfig, x, *, is_local: bool,
                      positions, compute_dtype, causal: bool = True,
                      xkv=None, positions_kv=None, part=None):
    """Full-sequence attention (training / prefill / encoder / cross).

    Returns (out, (k, v)) — k/v are RoPE-applied and cacheable.
    """
    xkv = x if xkv is None else xkv
    positions_kv = positions if positions_kv is None else positions_kv
    q, k, v = _project_qkv(p, cfg, x, xkv, positions, positions_kv, compute_dtype)
    k_cache, v_cache = k, v  # un-repeated, for the decode cache
    n_heads_eff = cfg.n_heads
    if part is not None:
        # GQA tensor-parallel layout selection:
        #  1. kv-heads divisible by 'model'  -> shard kv heads (grouped layout)
        #  2. q-heads divisible              -> repeat-KV to H heads, shard those
        #  3. otherwise -> repeat-KV, zero-pad heads up to the axis, shard
        #     (padded heads are sliced off before o_proj: exact)
        n_model = part.logical_size("heads")
        B_, Sq, K, G, D = q.shape
        if n_model > 1 and K % n_model == 0:
            q = part.act(q, ("batch", None, "heads", None, None))
            k = part.act(k, ("batch", None, "heads", None))
            v = part.act(v, ("batch", None, "heads", None))
        elif n_model > 1:
            H = K * G
            h_pad = (-(-H // n_model) * n_model) - H
            q = q.reshape(B_, Sq, H, 1, D)
            if G > 1:
                k = jnp.repeat(k, G, axis=2)
                v = jnp.repeat(v, G, axis=2)
            if h_pad:
                zq = ((0, 0), (0, 0), (0, h_pad), (0, 0), (0, 0))
                zk = ((0, 0), (0, 0), (0, h_pad), (0, 0))
                q = jnp.pad(q, zq)
                k = jnp.pad(k, zk)
                v = jnp.pad(v, zk)
                n_heads_eff = H + h_pad
            q = part.act(q, ("batch", None, "heads", None, None))
            k = part.act(k, ("batch", None, "heads", None))
            v = part.act(v, ("batch", None, "heads", None))
    window = cfg.window if is_local else 0
    backend = kdispatch.negotiated_model_backend(cfg.resolved_kernel_backend)
    if part is None and backend is not None:
        # registry-dispatched kernel (kernels/flash_attention.py) — local
        # path; the SPMD path uses the numerically-identical XLA flash
        # (tested equal), since a pallas_call inside pjit would need
        # shard_map. Shapes the kernel can't serve negotiate down to ref.
        from repro.kernels.ops import flash_attention as _reg_fa
        B_, Sq_, K_, G_, D_ = q.shape
        Skv_ = k.shape[1]
        qf = q.transpose(0, 2, 3, 1, 4).reshape(B_ * K_ * G_, Sq_, D_)
        kf = k.transpose(0, 2, 1, 3).reshape(B_ * K_, Skv_, D_)
        vf = v.transpose(0, 2, 1, 3).reshape(B_ * K_, Skv_, D_)
        with kdispatch.use_backend(backend):
            of = _reg_fa(qf, kf, vf, causal=causal, window=window,
                         cap=cfg.attn_softcap, scale=_scale(cfg))
        out = of.reshape(B_, K_, G_, Sq_, D_).transpose(0, 3, 1, 2, 4)
        out = out.astype(q.dtype)
    else:
        out = flash_attention_xla(
            q, k, v, causal=causal, window=window, cap=cfg.attn_softcap,
            scale=_scale(cfg), q_chunk=cfg.attn_chunk, kv_chunk=cfg.attn_chunk)
    B, S = x.shape[:2]
    hd = cfg.resolved_head_dim
    out = out.reshape(B, S, -1, hd)[:, :, :cfg.n_heads].reshape(
        B, S, cfg.n_heads * hd)
    if part is not None:
        out = part.act(out, ("batch", None, "mlp"))
    out = (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x.dtype)
    return out, (k_cache, v_cache)


def attention_decode(p: Params, cfg: ModelConfig, x, cache: dict, *,
                     is_local: bool, pos, compute_dtype, part=None,
                     cross: bool = False, active=None, block_tables=None,
                     layer=None):
    """Single-token decode against a cache.

    cache: {"k": (B, S_buf, K, D), "v": ..., ["slot_pos": (S_buf,) implicit]}
    For local layers S_buf == window (ring buffer); global layers S_buf == max
    sequence length, optionally sharded over 'data' (context parallelism).
    ``pos``: absolute position of the incoming token — scalar int32 (all
    sequences aligned, the dry-run path) or (B,) int32 (per-slot positions,
    the continuous-batching serve path).

    ``block_tables`` ((B, P) int32) selects the *paged* layout: cache k/v are
    global (n_blocks, page, K, D) pools and position ``p`` of slot ``b``
    lives at row ``p % page`` of block ``tables[b, p // page]``; the read
    dispatches through the ``paged_attention`` registry op. ``active``
    ((B,) bool) gates cache writes per slot — inactive/prefilling slots
    route their write out of bounds (dropped), so a pooled decode step never
    scribbles on a slot that is not in the decode phase. ``layer`` (int32
    scalar) marks paged pools stacked over the scanned layers,
    ``(n_rep, n_blocks, page, K, D)``: this layer's rows are written and
    read at that index of the stacked buffer (see ``_paged_decode``).
    Returns (out, new_cache).
    """
    vec_pos = jnp.ndim(pos) > 0  # per-slot positions (continuous batching)
    B = x.shape[0]
    hd, H, K = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    if cross:
        q = (x.astype(compute_dtype)
             @ p["q_proj"]["kernel"].astype(compute_dtype)).reshape(
                 B, 1, K, H // K, hd)
        k_all, v_all = cache["k"], cache["v"]
        new_cache = cache
        if cfg.qk_norm:
            q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        S_buf = k_all.shape[1]
        slot_pos = jnp.arange(S_buf)
        valid = slot_pos[None, :] < cache.get("len", S_buf)
    else:
        posb = (pos[:, None].astype(jnp.int32) if vec_pos
                else jnp.full((B, 1), pos, jnp.int32))
        q, k, v = _project_qkv(p, cfg, x, x, posb, posb, compute_dtype)
        if block_tables is not None:
            posv = pos if vec_pos else jnp.full((B,), pos, jnp.int32)
            return _paged_decode(p, cfg, q, k, v, cache, pos=posv,
                                 active=active, block_tables=block_tables,
                                 compute_dtype=compute_dtype, x_dtype=x.dtype,
                                 part=part, layer=layer)
        S_buf = cache["k"].shape[1]
        is_ring = is_local and cfg.window and S_buf == cfg.window
        if is_ring:
            slot = jnp.mod(pos, S_buf)
            # ring buffer: slot j holds absolute position p = pos - ((pos - j) mod S_buf)
            j = jnp.arange(S_buf)
            if vec_pos:
                slot_pos = pos[:, None] - jnp.mod(pos[:, None] - j[None, :], S_buf)
                slot_pos = jnp.where(j[None, :] == slot[:, None],
                                     pos[:, None], slot_pos)
            else:
                slot_pos = pos - jnp.mod(pos - j, S_buf)
                slot_pos = jnp.where(j == slot, pos, slot_pos)
        else:
            slot = pos
            slot_pos = jnp.arange(S_buf)
            if vec_pos:
                slot_pos = jnp.broadcast_to(slot_pos[None, :], (B, S_buf))
        if vec_pos:
            # per-slot write positions -> batched scatter; slots not in the
            # decode phase route their write out of bounds (dropped)
            bidx = jnp.arange(B)
            slot_w = slot if active is None else jnp.where(active, slot, S_buf)
            k_all = cache["k"].at[bidx, slot_w].set(
                k[:, 0].astype(cache["k"].dtype), mode="drop")
            v_all = cache["v"].at[bidx, slot_w].set(
                v[:, 0].astype(cache["v"].dtype), mode="drop")
        else:
            k_all = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
            v_all = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
        new_cache = {"k": k_all, "v": v_all}
        posc = pos[:, None] if vec_pos else pos
        valid = (slot_pos <= posc) & (slot_pos >= 0)
        if is_local and cfg.window:
            valid &= slot_pos > posc - cfg.window
        if not vec_pos:
            valid = valid[None, :]
    if part is not None:
        axis = "kv" if (not cross and not (is_local and S_buf == cfg.window)) else None
        k_all = part.act(k_all, ("batch", axis, "heads", None))
        v_all = part.act(v_all, ("batch", axis, "heads", None))
    s = jnp.einsum("bokgd,bskd->bkgos", q, k_all.astype(compute_dtype),
                   preferred_element_type=jnp.float32) * _scale(cfg)
    s = softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgos,bskd->bokgd", w.astype(compute_dtype),
                     v_all.astype(compute_dtype),
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, 1, H * hd).astype(compute_dtype)
    out = (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x.dtype)
    return out, new_cache


def _write_kv_rows(cfg: ModelConfig, pool: dict, idx, k, v):
    """Scatter new K/V rows into the paged ``pool`` at ``idx`` (a tuple of
    index arrays; out-of-bounds rows drop). A quantized pool (it carries
    ``k_scale``/``v_scale``, see ``models/cache.py``) takes each (row,
    head) quantized with its own absmax scale. Returns (the new pool, the
    rows as stored)."""
    rows = {"k": k, "v": v}
    if "k_scale" in pool:
        from repro.quant import quantize_kv
        for n in ("k", "v"):
            rows[n], rows[n + "_scale"] = quantize_kv(rows[n],
                                                      str(cfg.kv_dtype))
    rows = {n: r.astype(pool[n].dtype) for n, r in rows.items()}
    return ({n: pool[n].at[idx].set(r, mode="drop") for n, r in rows.items()},
            rows)


def _read_kv(pool: dict, compute_dtype):
    """K and V of stored pool rows (``{"k", "v"[, "k_scale", "v_scale"]}``
    indexed alike) in ``compute_dtype``, dequantized when scales ride
    along."""
    if "k_scale" not in pool:
        return pool["k"].astype(compute_dtype), pool["v"].astype(compute_dtype)
    from repro.quant import dequantize_kv
    return tuple(dequantize_kv(pool[n], pool[n + "_scale"], compute_dtype)
                 for n in ("k", "v"))


def _attend_chunk(p: Params, cfg: ModelConfig, q, k_old, v_old, k, v, mask,
                  compute_dtype, x_dtype):
    """Softmax of T queries over ``[snapshot | chunk]`` keys, then W_o.
    q: (B, T, K, G, D); k_old/v_old: (B, S, K, D); k/v: (B, T, K, D);
    mask: (B or 1, T, S + T). Returns (B, T, d)."""
    B, T = q.shape[:2]
    s_old = jnp.einsum("btkgd,bskd->bkgts", q, k_old.astype(compute_dtype),
                       preferred_element_type=jnp.float32) * _scale(cfg)
    s_new = jnp.einsum("btkgd,bskd->bkgts", q, k.astype(compute_dtype),
                       preferred_element_type=jnp.float32) * _scale(cfg)
    s = softcap(jnp.concatenate([s_old, s_new], axis=-1), cfg.attn_softcap)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    vv = jnp.concatenate([v_old, v], axis=1).astype(compute_dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w.astype(compute_dtype), vv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, T, -1).astype(compute_dtype)
    return (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x_dtype)


def _paged_decode(p: Params, cfg: ModelConfig, q, k, v, cache, *, pos, active,
                  block_tables, compute_dtype, x_dtype, part=None,
                  layer=None):
    """Single-token decode against the block-pool (paged) KV layout.

    q: (B, 1, K, G, D), k/v: (B, 1, K, D) — already projected, normed, and
    RoPE'd by ``attention_decode``; pos: (B,) int32. cache:
    {"k"/"v": (N, page, K, D)} global pools of one layer, or with ``layer``
    (int32 scalar) the pools of every scanned layer stacked,
    (n_rep, N, page, K, D). The new token's K/V scatter into the slot's
    current block of that layer (inactive slots dropped via an
    out-of-bounds block id) and the read gathers the slot's pages through
    ``ops.paged_attention``, which takes the stacked pool and the layer: a
    layer scan that carries the stacked pool updates it in place, and no
    layer's pool is ever sliced out or restacked. A one-layer pool is the
    leading-1 case at layer 0.

    Quantized pools (cache also carries ``k_scale``/``v_scale``): the
    incoming row is quantized before the scatter (``_write_kv_rows``) and
    the registry read dequantizes — KV crosses HBM at storage width both
    ways.
    """
    B = q.shape[0]
    hd, H = cfg.resolved_head_dim, cfg.n_heads
    stacked = layer is not None
    pools = dict(cache) if stacked else {n: a[None] for n, a in cache.items()}
    layer = jnp.asarray(layer if stacked else 0, jnp.int32)
    n_blocks, page = pools["k"].shape[1:3]
    blk = jnp.take_along_axis(block_tables, (pos // page)[:, None],
                              axis=1)[:, 0]
    if active is not None:
        blk = jnp.where(active, blk, n_blocks)  # OOB -> write dropped
    new, _ = _write_kv_rows(cfg, pools, (layer, blk, pos % page), k[:, 0],
                            v[:, 0])
    # registry read: an enclosing use_backend scope / cfg.kernel_backend
    # routes through the Pallas kernel; otherwise pin the gather-based ref
    # oracle (the XLA path) — ambient selection (env var / TPU auto) must
    # not reroute a model graph without explicit opt-in
    from repro.kernels.ops import paged_attention as _reg_pa
    be = (kdispatch.negotiated_model_backend(cfg.resolved_kernel_backend)
          or "ref")
    # serve-mode partitioner with KV-head-sharded pools: advertise the
    # layout so negotiation picks the shard_map'd impl (communication-free
    # per-shard reads); replicated pools fall through to the local paths
    serve_kv = (part.serve_kv_scope() if part is not None
                and getattr(part, "mode", None) == "serve"
                else contextlib.nullcontext())
    with serve_kv, kdispatch.use_backend(be):
        out = _reg_pa(q[:, 0], new["k"], new["v"], block_tables, pos + 1,
                      layer, new.get("k_scale"), new.get("v_scale"),
                      scale=_scale(cfg), cap=cfg.attn_softcap)
    out = out.reshape(B, 1, H * hd).astype(compute_dtype)
    out = (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x_dtype)
    if not stacked:
        new = {n: a[0] for n, a in new.items()}
    return out, new


def attention_paged_extend(p: Params, cfg: ModelConfig, x, cache: dict, *,
                           pos, n_valid, active, block_tables,
                           compute_dtype):
    """Score T new tokens per slot in ONE pass against the paged pools:
    chunked prefill (one slot, through its table row, ``active`` None),
    the verifier side of speculative decoding and the fork re-decode (every
    slot).

    x: (B, T, d) — slot b's tokens sit at absolute positions
    ``pos[b] .. pos[b]+T-1``; only the first ``n_valid[b]`` are real (the
    rest are padding whose writes drop and whose outputs are junk).
    ``pos``/``n_valid`` are (B,) int32; ``active`` ((B,) bool or None)
    gates whole slots exactly like decode. ``cache`` is one layer's pools
    ``{"k"/"v": (N, page, K, D)[, "k_scale"/"v_scale"]}`` and
    ``block_tables`` (B, P) the slots' tables. Rows past a slot's table
    (positions beyond ``P * page``) also drop, so a speculative chunk near
    ``max_len`` cannot scribble out of range.

    The chunk's rows are written (quantized for quantized pools); the
    queries read a pre-write snapshot of each slot's pages (positions below
    ``pos[b]``, prefix-shared pages included) and the chunk's own rows as
    stored, causal within the chunk with the ragged tail masked out.
    Returns (out (B, T, d), new_cache).
    """
    B, T = x.shape[:2]
    hd, K = cfg.resolved_head_dim, cfg.n_kv_heads
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # (B, T)
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, compute_dtype)
    n_blocks, page = cache["k"].shape[:2]
    n_pages = block_tables.shape[1]
    i = jnp.arange(T)
    valid_q = (i[None, :] < n_valid[:, None]) & (positions < n_pages * page)
    if active is not None:
        valid_q &= active[:, None]
    pg = jnp.clip(positions // page, 0, n_pages - 1)
    blk = jnp.take_along_axis(block_tables, pg, axis=1)       # (B, T)
    blk = jnp.where(valid_q, blk, n_blocks)                   # pads dropped
    new_cache, stored = _write_kv_rows(cfg, cache, (blk, positions % page),
                                       k, v)
    k, v = _read_kv(stored, compute_dtype)
    k_old, v_old = (a.reshape(B, n_pages * page, K, hd) for a in _read_kv(
        {n: a[block_tables] for n, a in cache.items()}, compute_dtype))
    # snapshot rows strictly below the slot's own pos (the prefix-shared
    # head is readable from 0); intra-chunk causal over the new keys with
    # the ragged tail masked out
    old_pos = jnp.arange(n_pages * page)
    mask_old = jnp.broadcast_to(old_pos[None, None, :] < pos[:, None, None],
                                (B, T, old_pos.shape[0]))
    mask_new = ((i[None, None, :] <= i[None, :, None])
                & (i[None, None, :] < n_valid[:, None, None]))
    mask = jnp.concatenate([mask_old, mask_new], axis=-1)
    return _attend_chunk(p, cfg, q, k_old, v_old, k, v, mask, compute_dtype,
                         x.dtype), new_cache


def attention_extend(p: Params, cfg: ModelConfig, x, cache: dict, *,
                     is_local: bool, pos, n_valid, slot, compute_dtype,
                     first_new_pos=0):
    """Extend ONE slot's dense or ring-buffer cache by up to T tokens
    (chunked prefill); paged pools go through ``attention_paged_extend``.

    x: (1, T, d) tokens at absolute positions ``pos .. pos+T-1``; the first
    ``n_valid`` are real, the rest are ragged-tail padding — their cache
    writes are dropped (out-of-bounds scatter) and their outputs are junk
    that the caller slices off. ``cache`` is the POOL entry, ``(B, S_buf,
    K, D)`` buffers; ``slot`` is this request's slot.

    Attention reads combine a pre-write snapshot of the slot's cache (old
    positions ``< pos``) with the chunk's own K/V under an intra-chunk
    causal (and sliding-window) mask — so ring buffers stay exact even when
    the chunk wraps the window.

    ``first_new_pos`` (traced scalar) is the absolute position prefill
    started at: nothing below it was written by this request, so the
    snapshot mask keeps those stale rows of a reused slot out of the
    scores. Returns (out (1, T, d), new_cache).
    """
    T = x.shape[1]
    hd, K = cfg.resolved_head_dim, cfg.n_kv_heads
    positions = pos + jnp.arange(T, dtype=jnp.int32)          # (T,) absolute
    q, k, v = _project_qkv(p, cfg, x, x, positions[None], positions[None],
                           compute_dtype)
    i = jnp.arange(T)
    valid_q = i < n_valid
    S_buf = cache["k"].shape[1]
    is_ring = is_local and cfg.window and S_buf == cfg.window
    k_old = jax.lax.dynamic_slice(cache["k"], (slot, 0, 0, 0),
                                  (1, S_buf, K, hd))
    v_old = jax.lax.dynamic_slice(cache["v"], (slot, 0, 0, 0),
                                  (1, S_buf, K, hd))
    j = jnp.arange(S_buf)
    if is_ring:
        # ring slot j held absolute position (pos-1) - ((pos-1-j) mod W)
        # before this chunk; only the last min(W, n_valid) chunk rows
        # are written (earlier rows would be overwritten by the wrap)
        old_pos = (pos - 1) - jnp.mod(pos - 1 - j, S_buf)
        w_ok = valid_q & (i >= n_valid - S_buf)
        rows = jnp.where(w_ok, positions % S_buf, S_buf)
    else:
        old_pos = j
        rows = jnp.where(valid_q, positions, S_buf)
    k_new = k_old.at[0, rows].set(k[0].astype(k_old.dtype), mode="drop")
    v_new = v_old.at[0, rows].set(v[0].astype(v_old.dtype), mode="drop")
    new_cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k_new, (slot, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v_new, (slot, 0, 0, 0)),
    }
    # scores over [old snapshot | chunk] keys; masks are (T, S_old) / (T, T)
    mask_old = ((old_pos >= first_new_pos) & (old_pos < pos))[None, :]
    mask_old = jnp.broadcast_to(mask_old, (T, S_buf))
    mask_new = i[None, :] <= i[:, None]                       # intra-chunk
    if is_local and cfg.window:
        mask_old = mask_old & (old_pos[None, :] > positions[:, None] - cfg.window)
        mask_new = mask_new & (i[:, None] - i[None, :] < cfg.window)
    mask = jnp.concatenate([mask_old, mask_new], axis=-1)[None]
    return _attend_chunk(p, cfg, q, k_old, v_old, k, v, mask, compute_dtype,
                         x.dtype), new_cache


# --------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# --------------------------------------------------------------------------
def mla_init(rng, cfg: ModelConfig, dtype) -> Params:
    a, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    ks = jax.random.split(rng, 4)
    p: Params = {"q_proj": {"kernel": dense_init(ks[0], d, H * a.qk_head_dim,
                                                 dtype)}}
    p["kv_a_proj"] = {"kernel": dense_init(ks[1], d, a.row_width, dtype)}
    p["kv_norm"] = norm_init(a.kv_lora_rank, "rmsnorm", dtype)
    p["kv_b_proj"] = {"kernel": dense_init(
        ks[2], a.kv_lora_rank, H * (a.qk_nope_head_dim + a.v_head_dim),
        dtype)}
    p["o_proj"] = {"kernel": dense_init(ks[3], H * a.v_head_dim, d, dtype)}
    return p


def mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: ``qk_head_dim^-0.5``, times ``mscale^2`` of YaRN's
    ``mscale_all_dim`` where it is set."""
    s = cfg.mla.qk_head_dim ** -0.5
    sc = cfg.rope_scaling
    if sc is not None and sc.mscale_all_dim:
        m = yarn_mscale(sc.factor, sc.mscale_all_dim)
        s *= m * m
    return s


def _mla_project(p: Params, cfg: ModelConfig, x, positions, compute_dtype):
    """x: (B, S, d); positions: (B, S) or (1, S). Returns q_nope (B, S, H,
    dn), RoPE'd q_pe (B, S, H, dr), the normed latent c_n (B, S, r) and the
    RoPE'd shared key k_pe (B, S, dr)."""
    a, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    xc = x.astype(compute_dtype)
    q = xc @ p["q_proj"]["kernel"].astype(compute_dtype)
    q = q.reshape(B, S, H, a.qk_head_dim)
    q_nope, q_pe = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    kv = xc @ p["kv_a_proj"]["kernel"].astype(compute_dtype)
    c = apply_norm(p["kv_norm"], kv[..., :a.kv_lora_rank], "rmsnorm",
                   cfg.norm_eps)
    k_pe = kv[..., a.kv_lora_rank:]
    if cfg.use_rope:
        q_pe, k_pe = _deinterleave(q_pe), _deinterleave(k_pe)
        q_pe = rope(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
        k_pe = rope(k_pe[:, :, None], positions, cfg.rope_theta,
                    cfg.rope_scaling)[:, :, 0]
    return q_nope, q_pe, c, k_pe


def _deinterleave(x):
    """Pairs ``(x0, x1), (x2, x3), ...`` to ``[x0, x2, ..., x1, x3, ...]``,
    as the published ``apply_rotary_pos_emb`` does before ``rotate_half``."""
    d = x.shape[-1]
    return jnp.swapaxes(x.reshape(x.shape[:-1] + (d // 2, 2)), -1,
                        -2).reshape(x.shape)


def _latent_rows(c, k_pe, width: int, dtype):
    """The cached rows ``[c_n, k_pe, 0...]`` (``width`` columns)."""
    pad = width - c.shape[-1] - k_pe.shape[-1]
    parts = [c.astype(dtype), k_pe.astype(dtype)]
    if pad:
        parts.append(jnp.zeros(c.shape[:-1] + (pad,), dtype))
    return jnp.concatenate(parts, axis=-1)


def _kv_b(p: Params, cfg: ModelConfig, compute_dtype):
    """W_kv_b as (r, H, dn + dv): per head, W_UK then W_UV."""
    a = cfg.mla
    return p["kv_b_proj"]["kernel"].astype(compute_dtype).reshape(
        a.kv_lora_rank, cfg.n_heads, a.qk_nope_head_dim + a.v_head_dim)


def _absorbed_q(p: Params, cfg: ModelConfig, q_nope, q_pe, width: int,
                compute_dtype):
    """``[q_nope W_UK_h, q_pe, 0...] · scale``: (B, T, H, width), the query
    each head scores latent rows with."""
    a = cfg.mla
    w_uk = _kv_b(p, cfg, compute_dtype)[..., :a.qk_nope_head_dim]
    q_lat = jnp.einsum("bthn,rhn->bthr", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    q = _latent_rows(q_lat, q_pe.astype(jnp.float32), width, jnp.float32)
    return (q * mla_scale(cfg)).astype(compute_dtype)


def _attend_latent(q, rows, mask, r: int):
    """Absorbed attention over latent rows. q: (B, T, H, W); rows: (B, S,
    W); mask: (B, T, S) True where row s is visible to query t. Returns
    (B, T, H, r) float32: per head, the softmax-weighted sum of c_n."""
    B, T, H, W = q.shape
    # (t, h) as one query axis: a plain batched matmul against the rows
    s = jnp.einsum("bqw,bsw->bqs", q.reshape(B, T * H, W),
                   rows.astype(q.dtype), preferred_element_type=jnp.float32)
    s = jnp.where(jnp.repeat(mask, H, axis=1), s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqs,bsr->bqr", w.astype(q.dtype),
                   rows[..., :r].astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return o.reshape(B, T, H, r)


def _mla_out(p: Params, cfg: ModelConfig, o_lat, compute_dtype, x_dtype):
    """(Σ p c_n) W_UV_h, heads concatenated, then W_o. o_lat: (B, T, H, r)."""
    a = cfg.mla
    B, T = o_lat.shape[:2]
    w_uv = _kv_b(p, cfg, compute_dtype)[..., a.qk_nope_head_dim:]
    o = jnp.einsum("bthr,rhv->bthv", o_lat.astype(compute_dtype), w_uv,
                   preferred_element_type=jnp.float32)
    o = o.reshape(B, T, cfg.n_heads * a.v_head_dim).astype(compute_dtype)
    return (o @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x_dtype)


def mla_forward(p: Params, cfg: ModelConfig, x, *, positions, compute_dtype,
                width: int):
    """Full-sequence latent attention (training / whole prefill), K and V
    decompressed per head. Returns (out, latent rows (B, S, width)) — the
    rows a decode cache holds."""
    a, H = cfg.mla, cfg.n_heads
    B, S, _ = x.shape
    q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x, positions, compute_dtype)
    kv = jnp.einsum("bsr,rhe->bshe", c.astype(compute_dtype),
                    _kv_b(p, cfg, compute_dtype))
    k_nope, v = kv[..., :a.qk_nope_head_dim], kv[..., a.qk_nope_head_dim:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None].astype(k_nope.dtype),
                                  (B, S, H, a.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], -1)
    D = max(a.qk_head_dim, a.v_head_dim)     # zero columns change nothing

    def pad(t):
        n = D - t.shape[-1]
        return jnp.pad(t, ((0, 0),) * 3 + ((0, n),)) if n else t

    out = flash_attention_xla(
        pad(q)[:, :, :, None], pad(k), pad(v), causal=True, window=0,
        cap=0.0, scale=mla_scale(cfg), q_chunk=cfg.attn_chunk,
        kv_chunk=cfg.attn_chunk)
    out = out[:, :, :, 0, :a.v_head_dim].reshape(B, S, H * a.v_head_dim)
    out = (out.astype(compute_dtype)
           @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x.dtype)
    return out, _latent_rows(c, k_pe, width, compute_dtype)


def _latent_pool(cache, layer):
    """The latent pool stacked over layers and the layer to index: a
    one-layer pool passes as the leading-1 stack at layer 0."""
    if layer is None:
        return cache["lat"][None], jnp.asarray(0, jnp.int32), False
    return cache["lat"], jnp.asarray(layer, jnp.int32), True


def mla_decode(p: Params, cfg: ModelConfig, x, cache: dict, *, pos,
               compute_dtype, active=None, block_tables=None, layer=None):
    """One absorbed decode token per slot against the latent cache.

    x: (B, 1, d); ``pos`` scalar or (B,) int32. Paged (``block_tables``):
    ``cache["lat"]`` is the latent pool ``(N, page, W)``, or with ``layer``
    the pools of every scanned layer stacked ``(n_rep, N, page, W)``; the
    new row scatters to ``[layer, block, row]`` (inactive slots dropped
    through an out-of-bounds block) and the read is the ``paged_mla``
    registry op, which takes the stacked pool and the layer, as
    ``_paged_decode`` does for K/V. Dense: ``cache["lat"]`` is ``(B,
    S_buf, W)``. Returns (out (B, 1, d), new cache entry)."""
    B = x.shape[0]
    r = cfg.mla.kv_lora_rank
    posv = (pos.astype(jnp.int32) if jnp.ndim(pos) > 0
            else jnp.full((B,), pos, jnp.int32))
    q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x, posv[:, None],
                                         compute_dtype)
    lat = cache["lat"]
    W = lat.shape[-1]
    row = _latent_rows(c[:, 0], k_pe[:, 0], W, lat.dtype)        # (B, W)
    q = _absorbed_q(p, cfg, q_nope, q_pe, W, compute_dtype)      # (B,1,H,W)
    if block_tables is not None:
        pool, li, stacked = _latent_pool(cache, layer)
        n_blocks, page = pool.shape[1:3]
        blk = jnp.take_along_axis(block_tables, (posv // page)[:, None],
                                  axis=1)[:, 0]
        if active is not None:
            blk = jnp.where(active, blk, n_blocks)    # OOB -> write dropped
        pool = pool.at[li, blk, posv % page].set(row, mode="drop")
        # registry read: cfg.kernel_backend / an enclosing use_backend
        # scope picks the Pallas kernel; otherwise the gather oracle
        from repro.kernels.ops import paged_mla as _reg_mla
        be = (kdispatch.negotiated_model_backend(cfg.resolved_kernel_backend)
              or "ref")
        with kdispatch.use_backend(be):
            o = _reg_mla(q[:, 0], pool, block_tables, posv + 1, li, dv=r)
        o = o[:, None].astype(jnp.float32)
        new = {"lat": pool if stacked else pool[0]}
    else:
        S_buf = lat.shape[1]
        w = posv if active is None else jnp.where(active, posv, S_buf)
        lat = lat.at[jnp.arange(B), w].set(row, mode="drop")
        valid = jnp.arange(S_buf)[None, :] <= posv[:, None]      # (B, S)
        o = _attend_latent(q, lat, valid[:, None], r)
        new = {"lat": lat}
    return _mla_out(p, cfg, o, compute_dtype, x.dtype), new


def mla_extend(p: Params, cfg: ModelConfig, x, cache: dict, *, pos, n_valid,
               slot, compute_dtype, block_tables=None, layer=None):
    """Extend ONE slot's latent cache by up to T tokens (chunked prefill).
    x: (1, T, d) at positions ``pos .. pos+T-1``, the first ``n_valid`` real
    (padding rows write nothing). The chunk's rows are written first; then
    every query reads the slot's rows at positions up to its own (rows of
    an earlier chunk, of prefix-shared pages, and of this chunk), so no
    snapshot is needed. The rows are read in blocks of
    :data:`EXTEND_KEY_BLOCK`, decompressed per head (K and V, as the full
    forward; this measured a little faster than scoring the latent rows
    absorbed on a TPU v5e) and folded in by an online softmax, over the
    blocks up to ``pos + T`` only: a chunk's cost follows its position, not
    the table's capacity. Paged pools may be stacked over layers
    (``layer``), as in :func:`mla_decode`. Returns (out, new cache entry).
    """
    a = cfg.mla
    T = x.shape[1]
    H, r = cfg.n_heads, a.kv_lora_rank
    dn, dr, dv = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    positions = pos + jnp.arange(T, dtype=jnp.int32)
    q_nope, q_pe, c, k_pe = _mla_project(p, cfg, x, positions[None],
                                         compute_dtype)
    lat = cache["lat"]
    W = lat.shape[-1]
    rows = _latent_rows(c[0], k_pe[0], W, lat.dtype)             # (T, W)
    valid_q = jnp.arange(T) < n_valid
    if block_tables is not None:
        pool, li, stacked = _latent_pool(cache, layer)
        n_blocks, page = pool.shape[1:3]
        n_pages = block_tables.shape[1]
        trow = jax.lax.dynamic_slice(block_tables, (slot, 0),
                                     (1, n_pages))[0]
        blk = trow[jnp.minimum(positions // page, n_pages - 1)]
        blk = jnp.where(valid_q, blk, n_blocks)       # pads dropped
        pool = pool.at[li, blk, positions % page].set(rows, mode="drop")
        new = {"lat": pool if stacked else pool[0]}
        kb = page * math.gcd(n_pages, max(1, EXTEND_KEY_BLOCK // page))

        def read(start):
            pages = jax.lax.dynamic_slice_in_dim(trow, start // page,
                                                 kb // page)
            return pool[li, pages].reshape(kb, W)
        n_rows = n_pages * page
    else:
        S_buf = lat.shape[1]
        lat = lat.at[slot, jnp.where(valid_q, positions, S_buf)].set(
            rows, mode="drop")
        new = {"lat": lat}
        kb = math.gcd(S_buf, EXTEND_KEY_BLOCK)

        def read(start):
            return jax.lax.dynamic_slice(lat, (slot, start, 0),
                                         (1, kb, W))[0]
        n_rows = S_buf
    q = jnp.concatenate([q_nope[0], q_pe[0].astype(q_nope.dtype)], -1)
    w_kv = _kv_b(p, cfg, compute_dtype)                  # (r, H, dn + dv)
    scale = mla_scale(cfg)

    def body(i, carry):
        m, l, acc = carry
        ctx = read(i * kb)                                       # (kb, W)
        kv = jnp.einsum("sr,rhe->she", ctx[:, :r].astype(compute_dtype),
                        w_kv)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            ctx[:, None, r:r + dr].astype(compute_dtype), (kb, H, dr))], -1)
        sc = jnp.einsum("thd,shd->hts", q, k,
                        preferred_element_type=jnp.float32) * scale
        kpos = i * kb + jnp.arange(kb)
        sc = jnp.where(kpos[None, None, :] <= positions[None, :, None], sc,
                       NEG_INF)
        m_new = jnp.maximum(m, sc.max(-1))
        pr = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "hts,shv->htv", pr.astype(compute_dtype), kv[..., dn:],
            preferred_element_type=jnp.float32)
        return m_new, l * corr + pr.sum(-1), acc

    n_kb = jnp.minimum((pos + T + kb - 1) // kb, n_rows // kb)
    init = (jnp.full((H, T), NEG_INF, jnp.float32),
            jnp.zeros((H, T), jnp.float32),
            jnp.zeros((H, T, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_kb, body, init)
    o = (acc / l[..., None]).transpose(1, 0, 2).reshape(1, T, H * dv)
    o = o.astype(compute_dtype) @ p["o_proj"]["kernel"].astype(compute_dtype)
    return o.astype(x.dtype), new
