"""Pure-jnp oracles for every Pallas kernel (the source of truth in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def gemm_ref(x, w, *, bias=None, scale=1.0, act=None):
    """Streaming GEMM with fused in-stream epilogue (paper C5b)."""
    out = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    if scale != 1.0:
        out = out * scale
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if act == "gelu":
        out = jax.nn.gelu(out, approximate=True)
    elif act == "silu":
        out = jax.nn.silu(out)
    return out


def gemm_wq_ref(x, qw, scales, bias=None, *, scale=1.0, act=None):
    """Dequantize-then-GEMM oracle for the weight-quantized ``gemm_wq``.

    qw: (K, N) int8/fp8 storage — or (K/2, N) int8 nibble-packed int4,
    recognized by the half-K shape relation against ``x`` and unpacked
    first; scales: (nb, N) fp32 per-block absmax scales with nb dividing K
    (nb == 1 => per-channel). The dequantized weight is materialized in
    fp32 — the negotiation fallback and the numerical source of truth for
    the in-tile-dequant Pallas kernel."""
    if qw.shape[0] * 2 == x.shape[-1] and qw.dtype == jnp.int8:
        from repro.quant.tensor import unpack_int4
        qw = unpack_int4(qw, axis=0)
    K, N = qw.shape
    nb = scales.shape[0]
    w = (qw.astype(jnp.float32).reshape(nb, K // nb, N)
         * scales.astype(jnp.float32)[:, None, :]).reshape(K, N)
    return gemm_ref(x, w, bias=bias, scale=scale, act=act)


def gemm_sparse_ref(x, w_or_vals, mask_or_idx, bias=None, *, scale=1.0,
                    act=None):
    """Dense-mask oracle for ``gemm_sparse`` — both structured layouts.

    Block-sparse: ``(x, w (K, N) float, mask (K/bs, N/bs) bool/int)`` —
    masked blocks zeroed, then the plain GEMM. 2:4: ``(x, vals (K/2, N),
    idx (K/2, N) int8)`` — densified with zeros at pruned positions. Either
    way the oracle materializes the exact dense weight the kernel consumes
    tile-by-tile, so parity is exact (identical per-element reassociation).
    """
    from repro.kernels.gemm_sparse import apply_block_mask, densify_24
    if (mask_or_idx.dtype == jnp.int8
            and mask_or_idx.shape == w_or_vals.shape):
        w = densify_24(w_or_vals, mask_or_idx)
    else:
        w = apply_block_mask(w_or_vals.astype(jnp.float32),
                             mask_or_idx != 0)
    return gemm_ref(x, w, bias=bias, scale=scale, act=act)


def flash_attention_ref(q, k, v, *, causal=True, window=0, cap=0.0, scale=None):
    """q: (BH, Sq, D); k, v: (BK, Skv, D) with BH % BK == 0. Plain softmax
    attention. GQA (BH = BK*G) is handled by a grouped reshape of q — the
    shared K/V heads are never materialized per query head."""
    BH, Sq, D = q.shape
    BK, Skv, _ = k.shape
    assert BH % BK == 0, (q.shape, k.shape)
    G = BH // BK
    scale = (1.0 / jnp.sqrt(D)) if scale is None else scale
    qg = q.reshape(BK, G, Sq, D)
    s = jnp.einsum("bgqd,bkd->bgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap:
        s = jnp.tanh(s / cap) * cap
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgqk,bkd->bgqd", p, v.astype(jnp.float32))
    return out.reshape(BH, Sq, D)


def paged_attention_ref(q, k_pool, v_pool, block_tables, lengths, layer,
                        k_scale=None, v_scale=None, *, scale=None, cap=0.0):
    """Gather-based paged decode attention. q: (B, K, G, D) one token per
    slot; k/v pools stacked over layers: (L, N, page, K, D), read at
    ``layer`` (int32 scalar); block_tables: (B, P) int32 pool block ids;
    lengths: (B,) int32 valid tokens (current included). The slot's
    sequence is materialized by gathering its pages straight out of the
    stacked pool — row ``p`` of the logical sequence is ``pool[layer,
    table[b, p // page], p % page]``.

    ``k_scale``/``v_scale`` ((L, N, page, K) float) mark *quantized* pools
    (int8/fp8 storage with per-row absmax scales): gathered rows are
    dequantized before scoring — the read-side half of the quantized paged
    KV cache (docs/quantization.md)."""
    B, K, G, D = q.shape
    page = k_pool.shape[2]
    P = block_tables.shape[1]
    k = k_pool[layer, block_tables].reshape(B, P * page, K, D)
    v = v_pool[layer, block_tables].reshape(B, P * page, K, D)
    if k_scale is not None:
        k = k.astype(jnp.float32) * k_scale[layer, block_tables].reshape(
            B, P * page, K).astype(jnp.float32)[..., None]
    if v_scale is not None:
        v = v.astype(jnp.float32) * v_scale[layer, block_tables].reshape(
            B, P * page, K).astype(jnp.float32)[..., None]
    scale = (1.0 / jnp.sqrt(D)) if scale is None else scale
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if cap:
        s = jnp.tanh(s / cap) * cap
    valid = jnp.arange(P * page)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_mla_ref(q, pool, block_tables, lengths, layer, *, dv):
    """Gather-based absorbed latent-attention decode. q: (B, H, D) one
    pre-scaled absorbed query per slot and head; pool: latent rows stacked
    over layers (L, N, page, D), read at ``layer``; block_tables: (B, P)
    int32; lengths: (B,) int32 rows per slot. Every head scores the slot's
    rows ``q · row`` and takes the softmax-weighted sum of ``row[:dv]``.
    Returns (B, H, dv)."""
    B, H, D = q.shape
    page = pool.shape[2]
    P = block_tables.shape[1]
    rows = pool[layer, block_tables].reshape(B, P * page, D)
    rows = rows.astype(jnp.float32)
    s = jnp.einsum("bhd,bsd->bhs", q.astype(jnp.float32), rows)
    valid = jnp.arange(P * page)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bsd->bhd", p, rows[..., :dv])
    return out.astype(q.dtype)


def lru_scan_ref(a, b, h0=None):
    """Diagonal recurrence h_t = a_t*h_{t-1} + b_t. a, b: (B, L, D)."""
    B, L, D = a.shape
    h0 = jnp.zeros((B, D), jnp.float32) if h0 is None else h0

    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    _, hs = jax.lax.scan(step, h0.astype(jnp.float32),
                         (a.astype(jnp.float32).transpose(1, 0, 2),
                          b.astype(jnp.float32).transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2)


def gather_rows_ref(table, idx):
    """Indexed row stream (paper C2/C5c): out[i] = table[idx[i]]."""
    return table[idx]


def instream_scale_reduce_ref(x, *, scale=1.0, shift=0.0):
    """In-stream DMA ops (paper C5b): y = scale*x + shift computed 'during the
    transfer', plus an in-stream arithmetic reduction (global sum)."""
    y = x.astype(jnp.float32) * scale + shift
    return y, jnp.sum(y)


def spmm_gather_ref(values, col_idx, dense, seg_ids, n_rows):
    """SpMM via gather + segment-sum (COO rows sorted): out[r] = Σ v·B[col]."""
    gathered = dense[col_idx] * values[:, None].astype(dense.dtype)
    return jax.ops.segment_sum(gathered, seg_ids, num_segments=n_rows)
