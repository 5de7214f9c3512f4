"""Absorbed multi-head latent attention (MLA) decode over a paged latent pool.

DeepSeek-V2's attention caches one latent row per token and layer: the
RMS-normed compressed KV ``c`` (``kv_lora_rank`` wide) followed by the
RoPE'd shared key ``k_pe`` (``qk_rope_head_dim`` wide), 576 columns at
DeepSeek-V2-Lite's widths. With the key and value up-projections absorbed
into the query and the output (``models/attention.py``), decode is attention
in which every query head reads the *same* row: the score of head ``h``
against row ``s`` is ``q[h] · row[s]`` (the absorbed query is ``[q_nope
W_UK_h, q_pe]``) and the value is the row's first ``dv`` columns, ``c``.

Kernel layout: q ``(B, H, D)`` (one token per slot, pre-scaled), the pool
stacked over layers ``(L, N, page, D)`` in HBM, read at ``layer``; the output
``(B, H, dv)`` is the softmax-weighted sum of ``c`` per head. The grid is one
step per slot. The block table, the lengths and the layer are
scalar-prefetch operands, as in ``paged_attention``; the slot's pages are
copied from HBM by DMA, ``pages_per_step`` pages at a time into a
double-buffered VMEM tile, and the loop runs over the pages the slot holds
only (``ceil(length / page)``), so a slot's cost follows its length and not
the table's capacity. Each page is read once for all heads; the running
``(m, l, acc)`` of the online softmax stay in registers across the loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: pool pages copied and scored per loop step (512 rows at page 16): on a
#: TPU v5e, 54 slots of 6-16k rows read 342 GB/s at 16 pages, 424 at 32
PAGES_PER_STEP = 32


def _mla_kernel(tbl_ref, len_ref, lyr_ref, q_ref, pool_hbm, o_ref, buf, sem,
                *, page: int, n_pages: int, ppb: int, dv: int):
    b = pl.program_id(0)
    length = len_ref[b]
    layer = lyr_ref[0]
    rows = ppb * page
    n_chunks = (length + rows - 1) // rows

    def copies(chunk, slot):
        out = []
        for i in range(ppb):
            j = jnp.minimum(chunk * ppb + i, n_pages - 1)
            out.append(pltpu.make_async_copy(
                pool_hbm.at[layer, tbl_ref[b, j]],
                buf.at[slot, pl.ds(i * page, page)], sem.at[slot, i]))
        return out

    @pl.when(n_chunks > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    q = q_ref[0]                                         # (H, D)
    H = q.shape[0]

    def body(chunk, carry):
        m, l, acc = carry
        slot = chunk % 2

        @pl.when(chunk + 1 < n_chunks)
        def _next():
            for c in copies(chunk + 1, 1 - slot):
                c.start()

        for c in copies(chunk, slot):
            c.wait()
        kv = buf[slot]                                   # (rows, D)
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        pos = chunk * rows + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)          # (H, rows)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p.astype(kv.dtype), kv[:, :dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32),
            jnp.zeros((H, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_chunks, body, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-37)).astype(o_ref.dtype)


def paged_mla(q, pool, block_tables, lengths, layer, *, dv: int,
              pages_per_step: int = PAGES_PER_STEP,
              interpret: bool = False):
    """q: (B, H, D) absorbed queries, softmax scale already applied; pool:
    latent rows stacked over layers (L, N, page, D), read at ``layer``
    (int32 scalar, traced or not); block_tables: (B, P) int32 pool block
    ids; lengths: (B,) int32 rows per slot (the current token included; 0
    leaves the slot's output at zero). Returns (B, H, dv): per head, the
    softmax over the slot's rows of ``q · row`` applied to ``row[:dv]``."""
    B, H, D = q.shape
    page = pool.shape[2]
    P = block_tables.shape[1]
    ppb = max(1, min(pages_per_step, P))
    kernel = functools.partial(_mla_kernel, page=page, n_pages=P, ppb=ppb,
                               dv=dv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,               # block_tables, lengths, layer
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, D), lambda b, tbl, ln, ly: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, H, dv), lambda b, tbl, ln, ly: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, ppb * page, D), pool.dtype),
                        pltpu.SemaphoreType.DMA((2, ppb))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, dv), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      jnp.reshape(layer, (1,)).astype(jnp.int32), q, pool)
