"""Attention: GQA with RoPE, sliding windows, logit softcaps, qk-norm.

Training/prefill uses a chunked FlashAttention-2-style online-softmax scan in
pure jnp (``flash_attention_xla``) — this is both the production XLA path for
the CPU dry-run and the numerical oracle for the Pallas kernel
(kernels/flash_attention.py). The paper itself leverages FlashAttention-2 for
its GPT-J inference evaluation (§II-C), so this layer is paper-faithful.

Decode uses a single-query scoring path against a (possibly length-sharded)
KV cache — the context-parallel cache is the framework's analogue of spreading
Occamy's HBM channels across Ramora's mesh edge routers.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import dispatch as kdispatch
from repro.models.layers import Params, apply_norm, dense_init, norm_init, rope, softcap

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


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
        qf = rope(qf, positions_q, cfg.rope_theta)
        q = qf.reshape(B, Sq, K, G, hd)
        k = rope(k, positions_kv, cfg.rope_theta)
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
                     cross: bool = False, active=None, block_tables=None):
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
    scribbles on a slot that is not in the decode phase.
    Returns (out, new_cache).
    """
    vec_pos = jnp.ndim(pos) > 0  # per-slot positions (continuous batching)
    B = x.shape[0]
    hd, H, K = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // K
    xc = x.astype(compute_dtype)  # (B, 1, d)
    q = (xc @ p["q_proj"]["kernel"].astype(compute_dtype)).reshape(B, 1, K, G, hd)
    if cross:
        k_all, v_all = cache["k"], cache["v"]
        new_cache = cache
        if cfg.qk_norm:
            q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        S_buf = k_all.shape[1]
        slot_pos = jnp.arange(S_buf)
        valid = slot_pos[None, :] < cache.get("len", S_buf)
    else:
        k = (xc @ p["k_proj"]["kernel"].astype(compute_dtype)).reshape(B, 1, K, hd)
        v = (xc @ p["v_proj"]["kernel"].astype(compute_dtype)).reshape(B, 1, K, hd)
        if cfg.qk_norm:
            q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
            k = apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
        if cfg.use_rope:
            posb = (pos[:, None].astype(jnp.int32) if vec_pos
                    else jnp.full((B, 1), pos, jnp.int32))
            qf = rope(q.reshape(B, 1, H, hd), posb, cfg.rope_theta)
            q = qf.reshape(B, 1, K, G, hd)
            k = rope(k, posb, cfg.rope_theta)
        if block_tables is not None:
            posv = pos if vec_pos else jnp.full((B,), pos, jnp.int32)
            return _paged_decode(p, cfg, q, k, v, cache, pos=posv,
                                 active=active, block_tables=block_tables,
                                 compute_dtype=compute_dtype, x_dtype=x.dtype,
                                 part=part)
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


def _paged_decode(p: Params, cfg: ModelConfig, q, k, v, cache, *, pos, active,
                  block_tables, compute_dtype, x_dtype, part=None):
    """Single-token decode against the block-pool (paged) KV layout.

    q: (B, 1, K, G, D), k/v: (B, 1, K, D) — already projected, normed, and
    RoPE'd by ``attention_decode``; pos: (B,) int32. cache:
    {"k"/"v": (N, page, K, D)} global pools. The new token's K/V scatter
    into the slot's current block (inactive slots dropped via an
    out-of-bounds block id); the read gathers the slot's pages through
    ``ops.paged_attention``.

    Quantized pools (cache also carries ``k_scale``/``v_scale`` — see
    ``models/cache.py``): the incoming row is quantized per (slot, head)
    with its own absmax scale before the scatter, and the registry read
    dequantizes — KV crosses HBM at storage width both ways.
    """
    B = q.shape[0]
    hd, H = cfg.resolved_head_dim, cfg.n_heads
    pool_k, pool_v = cache["k"], cache["v"]
    quantized = "k_scale" in cache
    n_blocks, page = pool_k.shape[:2]
    blk = jnp.take_along_axis(block_tables, (pos // page)[:, None],
                              axis=1)[:, 0]
    if active is not None:
        blk = jnp.where(active, blk, n_blocks)  # OOB -> write dropped
    row = pos % page
    k_sc = v_sc = None
    if quantized:
        from repro.quant import quantize_kv
        kq, ks = quantize_kv(k[:, 0], str(cfg.kv_dtype))      # (B,K,hd),(B,K)
        vq, vs = quantize_kv(v[:, 0], str(cfg.kv_dtype))
        pool_k = pool_k.at[blk, row].set(kq.astype(pool_k.dtype), mode="drop")
        pool_v = pool_v.at[blk, row].set(vq.astype(pool_v.dtype), mode="drop")
        k_sc = cache["k_scale"].at[blk, row].set(
            ks.astype(cache["k_scale"].dtype), mode="drop")
        v_sc = cache["v_scale"].at[blk, row].set(
            vs.astype(cache["v_scale"].dtype), mode="drop")
    else:
        pool_k = pool_k.at[blk, row].set(k[:, 0].astype(pool_k.dtype),
                                         mode="drop")
        pool_v = pool_v.at[blk, row].set(v[:, 0].astype(pool_v.dtype),
                                         mode="drop")
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
        out = _reg_pa(q[:, 0], pool_k, pool_v, block_tables, pos + 1,
                      k_sc, v_sc, scale=_scale(cfg), cap=cfg.attn_softcap)
    out = out.reshape(B, 1, H * hd).astype(compute_dtype)
    out = (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x_dtype)
    new_cache = {"k": pool_k, "v": pool_v}
    if quantized:
        new_cache["k_scale"] = k_sc
        new_cache["v_scale"] = v_sc
    return out, new_cache


def attention_verify(p: Params, cfg: ModelConfig, x, cache: dict, *,
                     pos, n_valid, active, block_tables, compute_dtype):
    """Score T tokens per slot in ONE pass against the paged pools — the
    verifier side of speculative decoding, and the fork re-decode.

    x: (B, T, d) — slot b's tokens sit at absolute positions
    ``pos[b] .. pos[b]+T-1``; only the first ``n_valid[b]`` are real (the
    rest are padding whose writes drop and whose outputs are junk).
    ``active`` ((B,) bool) gates whole slots exactly like decode. Paged
    pools only: this is ``attention_extend``'s scatter/snapshot scheme
    batched over slots, with per-slot masks replacing the traced scalars.
    Rows past a slot's table (positions beyond ``P * page``) also drop, so
    a speculative chunk near ``max_len`` cannot scribble out of range.
    Returns (out (B, T, d), new_cache).
    """
    B, T = x.shape[:2]
    hd, H, K = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // K
    xc = x.astype(compute_dtype)
    q = (xc @ p["q_proj"]["kernel"].astype(compute_dtype)).reshape(B, T, K, G, hd)
    k = (xc @ p["k_proj"]["kernel"].astype(compute_dtype)).reshape(B, T, K, hd)
    v = (xc @ p["v_proj"]["kernel"].astype(compute_dtype)).reshape(B, T, K, hd)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]  # (B, T)
    if cfg.use_rope:
        qf = rope(q.reshape(B, T, H, hd), positions, cfg.rope_theta)
        q = qf.reshape(B, T, K, G, hd)
        k = rope(k, positions, cfg.rope_theta)

    pool_k, pool_v = cache["k"], cache["v"]
    n_blocks, page = pool_k.shape[:2]
    n_pages = block_tables.shape[1]
    i = jnp.arange(T)[None, :]                                # (1, T)
    valid_q = (i < n_valid[:, None]) & (positions < n_pages * page)
    if active is not None:
        valid_q &= active[:, None]
    pg = jnp.clip(positions // page, 0, n_pages - 1)
    blk = jnp.take_along_axis(block_tables, pg, axis=1)       # (B, T)
    blk_w = jnp.where(valid_q, blk, n_blocks)                 # pads dropped
    rows = positions % page
    if "k_scale" in cache:
        from repro.quant import dequantize_kv, quantize_kv
        kq, ksc = quantize_kv(k, str(cfg.kv_dtype))   # (B,T,K,hd), (B,T,K)
        vq, vsc = quantize_kv(v, str(cfg.kv_dtype))
        new_cache = {
            "k": pool_k.at[blk_w, rows].set(kq.astype(pool_k.dtype),
                                            mode="drop"),
            "v": pool_v.at[blk_w, rows].set(vq.astype(pool_v.dtype),
                                            mode="drop"),
            "k_scale": cache["k_scale"].at[blk_w, rows].set(
                ksc.astype(cache["k_scale"].dtype), mode="drop"),
            "v_scale": cache["v_scale"].at[blk_w, rows].set(
                vsc.astype(cache["v_scale"].dtype), mode="drop"),
        }
        # the chunk reads its own rows as stored, as decode and a later
        # prefix hit read them
        k = dequantize_kv(kq.astype(pool_k.dtype),
                          ksc.astype(cache["k_scale"].dtype), compute_dtype)
        v = dequantize_kv(vq.astype(pool_v.dtype),
                          vsc.astype(cache["v_scale"].dtype), compute_dtype)
        k_old = dequantize_kv(pool_k[block_tables],
                              cache["k_scale"][block_tables],
                              compute_dtype).reshape(B, n_pages * page, K, hd)
        v_old = dequantize_kv(pool_v[block_tables],
                              cache["v_scale"][block_tables],
                              compute_dtype).reshape(B, n_pages * page, K, hd)
    else:
        new_cache = {
            "k": pool_k.at[blk_w, rows].set(k.astype(pool_k.dtype),
                                            mode="drop"),
            "v": pool_v.at[blk_w, rows].set(v.astype(pool_v.dtype),
                                            mode="drop"),
        }
        k_old = pool_k[block_tables].reshape(B, n_pages * page, K, hd)
        v_old = pool_v[block_tables].reshape(B, n_pages * page, K, hd)
    old_pos = jnp.arange(n_pages * page)

    # per-slot masks: snapshot rows strictly below the slot's own pos (the
    # prefix-shared head is readable from 0, as in extend), intra-chunk
    # causal over the new keys with the ragged tail masked out
    mask_old = jnp.broadcast_to(
        (old_pos[None, None, :] < pos[:, None, None]),
        (B, T, old_pos.shape[0]))
    j = jnp.arange(T)
    mask_new = ((j[None, None, :] <= j[None, :, None])
                & (j[None, None, :] < n_valid[:, None, None]))
    s_old = jnp.einsum("btkgd,bskd->bkgts", q, k_old.astype(compute_dtype),
                       preferred_element_type=jnp.float32) * _scale(cfg)
    s_new = jnp.einsum("btkgd,bskd->bkgts", q, k.astype(compute_dtype),
                       preferred_element_type=jnp.float32) * _scale(cfg)
    s = softcap(jnp.concatenate([s_old, s_new], axis=-1), cfg.attn_softcap)
    mask = jnp.concatenate([mask_old, mask_new], axis=-1)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    vv = jnp.concatenate([v_old, v], axis=1).astype(compute_dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w.astype(compute_dtype), vv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(B, T, H * hd).astype(compute_dtype)
    out = (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x.dtype)
    return out, new_cache


def attention_extend(p: Params, cfg: ModelConfig, x, cache: dict, *,
                     is_local: bool, pos, n_valid, slot, compute_dtype,
                     block_tables=None, first_new_pos=0):
    """Extend ONE slot's cache by up to T tokens (chunked prefill).

    x: (1, T, d) tokens at absolute positions ``pos .. pos+T-1``; the first
    ``n_valid`` are real, the rest are ragged-tail padding — their cache
    writes are dropped (out-of-bounds scatter) and their outputs are junk
    that the caller slices off. ``cache`` is the POOL entry: dense
    ``(B, S_buf, K, D)`` buffers, or paged ``(n_blocks, page, K, D)`` pools
    with ``block_tables`` ((B, P) int32). ``slot`` is this request's slot.

    Attention reads combine a pre-write snapshot of the slot's cache (old
    positions ``< pos``) with the chunk's own K/V under an intra-chunk
    causal (and sliding-window) mask — so ring buffers stay exact even when
    the chunk wraps the window.

    ``first_new_pos`` (traced scalar) is the absolute position prefill
    started at: with prefix caching the paged snapshot rows below it were
    *mapped* from shared blocks (valid, readable mid-sequence), while in
    the dense layout nothing below it was ever written by this request —
    the snapshot mask keeps those stale rows of a reused slot out of the
    scores. Returns (out (1, T, d), new_cache).
    """
    T = x.shape[1]
    hd, H, K = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    G = H // K
    xc = x.astype(compute_dtype)
    q = (xc @ p["q_proj"]["kernel"].astype(compute_dtype)).reshape(1, T, K, G, hd)
    k = (xc @ p["k_proj"]["kernel"].astype(compute_dtype)).reshape(1, T, K, hd)
    v = (xc @ p["v_proj"]["kernel"].astype(compute_dtype)).reshape(1, T, K, hd)
    if cfg.qk_norm:
        q = apply_norm(p["q_norm"], q, "rmsnorm", cfg.norm_eps)
        k = apply_norm(p["k_norm"], k, "rmsnorm", cfg.norm_eps)
    positions = pos + jnp.arange(T, dtype=jnp.int32)          # (T,) absolute
    if cfg.use_rope:
        qf = rope(q.reshape(1, T, H, hd), positions[None], cfg.rope_theta)
        q = qf.reshape(1, T, K, G, hd)
        k = rope(k, positions[None], cfg.rope_theta)
    i = jnp.arange(T)
    valid_q = i < n_valid

    if block_tables is not None:
        # paged pools: scatter the chunk rows through the slot's block table
        pool_k, pool_v = cache["k"], cache["v"]
        n_blocks, page = pool_k.shape[:2]
        n_pages = block_tables.shape[1]
        table_row = jax.lax.dynamic_slice(
            block_tables, (slot, 0), (1, n_pages))[0]         # (P,)
        blk = table_row[positions // page]
        blk_w = jnp.where(valid_q, blk, n_blocks)             # pads dropped
        rows = positions % page
        if "k_scale" in cache:
            # quantized pools: per-row absmax quantize the chunk before the
            # scatter; the pre-write snapshot dequantizes at read
            from repro.quant import dequantize_kv, quantize_kv
            kq, ksc = quantize_kv(k[0], str(cfg.kv_dtype))    # (T,K,hd),(T,K)
            vq, vsc = quantize_kv(v[0], str(cfg.kv_dtype))
            new_cache = {
                "k": pool_k.at[blk_w, rows].set(kq.astype(pool_k.dtype),
                                                mode="drop"),
                "v": pool_v.at[blk_w, rows].set(vq.astype(pool_v.dtype),
                                                mode="drop"),
                "k_scale": cache["k_scale"].at[blk_w, rows].set(
                    ksc.astype(cache["k_scale"].dtype), mode="drop"),
                "v_scale": cache["v_scale"].at[blk_w, rows].set(
                    vsc.astype(cache["v_scale"].dtype), mode="drop"),
            }
            # the chunk reads its own rows as stored, as decode and a later
            # prefix hit read them
            k = dequantize_kv(kq.astype(pool_k.dtype),
                              ksc.astype(cache["k_scale"].dtype),
                              compute_dtype)[None]
            v = dequantize_kv(vq.astype(pool_v.dtype),
                              vsc.astype(cache["v_scale"].dtype),
                              compute_dtype)[None]
            k_old = dequantize_kv(pool_k[table_row],
                                  cache["k_scale"][table_row],
                                  compute_dtype).reshape(
                                      1, n_pages * page, K, hd)
            v_old = dequantize_kv(pool_v[table_row],
                                  cache["v_scale"][table_row],
                                  compute_dtype).reshape(
                                      1, n_pages * page, K, hd)
        else:
            new_cache = {
                "k": pool_k.at[blk_w, rows].set(k[0].astype(pool_k.dtype),
                                                mode="drop"),
                "v": pool_v.at[blk_w, rows].set(v[0].astype(pool_v.dtype),
                                                mode="drop"),
            }
            k_old = pool_k[table_row].reshape(1, n_pages * page, K, hd)
            v_old = pool_v[table_row].reshape(1, n_pages * page, K, hd)
        old_pos = jnp.arange(n_pages * page)                  # absolute
    else:
        S_buf = cache["k"].shape[1]
        is_ring = is_local and cfg.window and S_buf == cfg.window
        k_slot = jax.lax.dynamic_slice(cache["k"], (slot, 0, 0, 0),
                                       (1, S_buf, K, hd))
        v_slot = jax.lax.dynamic_slice(cache["v"], (slot, 0, 0, 0),
                                       (1, S_buf, K, hd))
        k_old, v_old = k_slot, v_slot
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
        k_new = k_slot.at[0, rows].set(k[0].astype(k_slot.dtype), mode="drop")
        v_new = v_slot.at[0, rows].set(v[0].astype(v_slot.dtype), mode="drop")
        new_cache = {
            "k": jax.lax.dynamic_update_slice(cache["k"], k_new,
                                              (slot, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(cache["v"], v_new,
                                              (slot, 0, 0, 0)),
        }

    # scores over [old snapshot | chunk] keys; masks are (T, S_old) / (T, T)
    # — paged snapshots are readable from position 0 (prefix-shared blocks
    # hold valid rows below first_new_pos); dense snapshots only from
    # first_new_pos (rows below it belong to the slot's previous occupant)
    snap_lo = 0 if block_tables is not None else first_new_pos
    mask_old = ((old_pos >= snap_lo) & (old_pos < pos))[None, :]
    mask_old = jnp.broadcast_to(mask_old, (T, old_pos.shape[0]))
    mask_new = i[None, :] <= i[:, None]                       # intra-chunk
    if is_local and cfg.window:
        mask_old = mask_old & (old_pos[None, :] > positions[:, None] - cfg.window)
        mask_new = mask_new & (i[:, None] - i[None, :] < cfg.window)
    s_old = jnp.einsum("btkgd,bskd->bkgts", q, k_old.astype(compute_dtype),
                       preferred_element_type=jnp.float32) * _scale(cfg)
    s_new = jnp.einsum("btkgd,bskd->bkgts", q, k.astype(compute_dtype),
                       preferred_element_type=jnp.float32) * _scale(cfg)
    s = softcap(jnp.concatenate([s_old, s_new], axis=-1), cfg.attn_softcap)
    mask = jnp.concatenate([mask_old, mask_new], axis=-1)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    vv = jnp.concatenate([v_old, v], axis=1).astype(compute_dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", w.astype(compute_dtype), vv,
                     preferred_element_type=jnp.float32)
    out = out.reshape(1, T, H * hd).astype(compute_dtype)
    out = (out @ p["o_proj"]["kernel"].astype(compute_dtype)).astype(x.dtype)
    return out, new_cache
