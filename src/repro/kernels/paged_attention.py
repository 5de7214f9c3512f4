"""Paged decode-attention Pallas TPU kernel (block-pool KV cache).

The serving cache is a global pool of fixed-size KV blocks — the software
analogue of Occamy's banked TCDM: many independent in-flight streams each own
a handful of fixed-size blocks instead of a statically reserved ``max_len``
region. Each decode query reads its sequence through a per-slot *block table*
(``(B, P)`` int32 of pool block ids, position ``p`` lives at row ``p %
page_size`` of block ``table[b, p // page_size]``).

Kernel layout: q ``(B, K, G, D)`` (one token per slot, GQA groups G), pools
stacked over layers, ``(L, N, page, K, D)``, read at layer ``layer``. The
model's layer scan carries every layer's pool in one buffer, so the kernel
indexes the layer itself and no per-layer slice of the pool is ever
materialized; a single layer's ``(N, page, K, D)`` pool passes as a
leading-1 reshape at layer 0. Grid ``(B, P)`` with the page dimension
innermost and sequential; each step fetches one page of all K heads and runs
the per-head dots batched over K. The block table, the sequence lengths and
the layer ride in as *scalar-prefetch* operands
(``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps can chase
the table — the pool block for grid step ``(b, j)`` is ``(layer, table[b,
j])``, fetched by DMA like any dense operand.
Running ``(m, l, acc)`` live in VMEM scratch across the page pass
(FlashAttention-style online softmax); pages wholly beyond the sequence
length are skipped with ``pl.when``, so decode cost scales with *allocated*
pages, not table capacity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_body(tbl_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             m_ref, l_ref, acc_ref, *, page: int, n_pages: int,
             scale: float, cap: float, out_dtype):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]
    base = j * page

    # pages beyond the sequence do no work: decode cost follows the block
    # table's allocated prefix, not its (max_len-sized) capacity
    @pl.when(base < length)
    def _page():
        q = q_ref[0]                                 # (K, G, D)
        k = k_ref[0, 0]                              # (page, K, D)
        v = v_ref[0, 0]
        # one page of every KV head per step: per-head dots batched over K
        # (q's axis 0, the pool tile's axis 1)
        if ks_ref is not None:
            # quantized pool: QK^T runs *on the storage codes* via a
            # mixed-input native dot (f32 x int8/fp8 -> f32), and the
            # per-row absmax scale, constant along D, factors out onto the
            # (K, G, page) scores. No float copy of the page is made.
            s = jax.lax.dot_general(
                q.astype(jnp.float32), k,
                (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32)
            s = s * jnp.swapaxes(ks_ref[0, 0], 0, 1)[:, None, :] * scale
        else:
            s = jax.lax.dot_general(
                q, k, (((2,), (2,)), ((0,), (1,))),
                preferred_element_type=jnp.float32) * scale  # (K, G, page)
        if cap:
            s = jnp.tanh(s / cap) * cap
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]                          # (K, G, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        if vs_ref is not None:
            # fold the per-v-row scale into the small probability tile, then
            # contract directly against the storage codes
            pv = p * jnp.swapaxes(vs_ref[0, 0], 0, 1)[:, None, :]
        else:
            pv = p.astype(v.dtype)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pv, v, (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-37)).astype(out_dtype)


def _pa_kernel(tbl_ref, len_ref, lyr_ref, q_ref, k_ref, v_ref, o_ref,
               m_ref, l_ref, acc_ref, **kw):
    del lyr_ref                                      # read by the index maps
    _pa_body(tbl_ref, len_ref, q_ref, k_ref, v_ref, None, None, o_ref,
             m_ref, l_ref, acc_ref, **kw)


def _pa_kernel_quant(tbl_ref, len_ref, lyr_ref, q_ref, k_ref, v_ref, ks_ref,
                     vs_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    del lyr_ref
    _pa_body(tbl_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
             m_ref, l_ref, acc_ref, **kw)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, layer,
                    k_scale=None, v_scale=None, *,
                    scale: float | None = None, cap: float = 0.0,
                    interpret: bool = False):
    """q: (B, K, G, D) single decode token per slot; k/v pools stacked over
    layers (L, N, page, K, D), read at ``layer`` (int32 scalar, traced or
    not); block_tables: (B, P) int32 pool block ids; lengths: (B,) int32
    valid tokens per slot (current token included). With
    ``k_scale``/``v_scale`` ((L, N, page, K) float) the pools are
    *quantized* (int8/fp8 storage): the kernel contracts directly against
    the storage codes with mixed-input native dots and folds the per-row
    absmax scales into the (K, G, page) score/probability tiles; the scale
    tiles chase the block table exactly like the pools. Returns
    (B, K, G, D)."""
    B, K, G, D = q.shape
    page = k_pool.shape[2]
    P = block_tables.shape[1]
    scale = (1.0 / (D ** 0.5)) if scale is None else scale
    quant = k_scale is not None
    kernel = functools.partial(
        _pa_kernel_quant if quant else _pa_kernel, page=page, n_pages=P,
        scale=scale, cap=cap, out_dtype=q.dtype)
    # one block = one page of all K heads of one layer: the last two block
    # dims (K, D) span the pool's own, as the TPU lowering requires
    pool_spec = pl.BlockSpec((1, 1, page, K, D),
                             lambda b, j, tbl, ln, ly: (ly[0], tbl[b, j],
                                                        0, 0, 0))
    head_spec = pl.BlockSpec((1, K, G, D),
                             lambda b, j, tbl, ln, ly: (b, 0, 0, 0))
    in_specs = [head_spec, pool_spec, pool_spec]
    args = [q, k_pool, v_pool]
    if quant:
        scale_spec = pl.BlockSpec(
            (1, 1, page, K),
            lambda b, j, tbl, ln, ly: (ly[0], tbl[b, j], 0, 0))
        in_specs += [scale_spec, scale_spec]
        # the pools keep float16 scales; the TPU has no float16 vector
        # loads, so the kernel reads them as float32
        args += [k_scale.astype(jnp.float32), v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # block_tables, lengths, layer
        grid=(B, P),
        in_specs=in_specs,
        out_specs=head_spec,
        scratch_shapes=[
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, 1), jnp.float32),
            pltpu.VMEM((K, G, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, G, D), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), *args)


def paged_attention_sharded(q, k_pool, v_pool, block_tables, lengths, layer,
                            k_scale=None, v_scale=None, *, mesh, axis: str,
                            scale: float | None = None, cap: float = 0.0):
    """Sharded paged decode attention: ``shard_map`` over mesh ``axis``.

    Serving shards the block pools by KV head over the model axis (the
    per-chiplet HBM slice of the paper's scale-out arc): stacked pools
    arrive ``(L, N, page, K/n, D)`` per shard, q replicated, block tables,
    lengths and the layer replicated. Each shard runs the *local* paged
    read over its own KV heads — heads are batch-like in decode attention,
    so the pass is communication-free; the (B, K, G, D) output shards over
    K and all-gathers only where downstream math (o_proj) needs the full
    head dim, which GSPMD inserts outside this body. Quantized pools carry
    their per-row scales sharded identically.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.ref import paged_attention_ref

    head_spec = P(None, axis, None, None)
    pool_spec = P(None, None, None, axis, None)
    scale_spec = P(None, None, None, axis)
    layer = jnp.asarray(layer, jnp.int32)

    if k_scale is not None:
        def body(ql, kl, vl, tbl, ln, ly, ksl, vsl):
            return paged_attention_ref(ql, kl, vl, tbl, ln, ly, ksl, vsl,
                                       scale=scale, cap=cap)
        sm = jax.shard_map(
            body, mesh=mesh,
            in_specs=(head_spec, pool_spec, pool_spec, P(), P(), P(),
                      scale_spec, scale_spec),
            out_specs=head_spec, check_vma=False)
        return sm(q, k_pool, v_pool, block_tables, lengths, layer, k_scale,
                  v_scale)

    def body(ql, kl, vl, tbl, ln, ly):
        return paged_attention_ref(ql, kl, vl, tbl, ln, ly,
                                   scale=scale, cap=cap)
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(head_spec, pool_spec, pool_spec, P(), P(), P()),
        out_specs=head_spec, check_vma=False)
    return sm(q, k_pool, v_pool, block_tables, lengths, layer)
