"""Weight-quantized streaming GEMM with in-tile dequantization (Pallas TPU).

Same schedule as the dense ``gemm`` kernel (gemm.py): grid
``(M/bm, N/bn, K/bk)`` with K innermost and a VMEM fp32 accumulator — the
Occamy cluster recipe (C1) — but the weight operand streams through HBM at
its *storage* width (int8, fp8-e4m3, or nibble-packed int4: half / quarter /
eighth the bf16 bytes — the paper's precision-halving bandwidth double) and
is dequantized **in-tile**, right after the DMA, the way Ogopogo's in-stream
DMA ops (C5b) apply elementwise work during the transfer.

``pack=2`` selects the int4 layout: the weight operand is ``(K/2, N)`` int8
bytes carrying two codes each (lo nibble = even K row, hi = odd), the tile
crosses HBM at half-byte-per-element width, and the kernel sign-extends the
nibbles with a shift pair before the dequant multiply — unpack happens in
VMEM, never in HBM.

Scales arrive pre-gathered per K-tile: the wrapper (ops.py) turns the
``(n_blocks, N)`` per-block scales into ``(n_k_tiles, N)`` rows — one row
per K grid step — so a scale tile never straddles a quant-block boundary
(the wrapper picks ``block_k`` to divide the quant block). The kernel views
the rows as ``(n_k_tiles, 1, N)`` and reads ``(1, 1, bn)`` tiles: their last
two dims span the array's ``(1, N)``, as the TPU lowering requires.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_tile(q_ref, s_ref, pack: int):
    """(bk/pack, bn) storage tile, (1, 1, bn) scale tile -> (bk, bn) fp32
    weight tile."""
    q = q_ref[...]
    if pack == 2:
        # sign-extending nibble unpack in int32 (the TPU's vector unit has
        # no int8 shifts): lo via shift-up/arith-shift-down, hi via
        # arithmetic shift; interleave restores the logical K order
        q = q.astype(jnp.int32)
        lo = (q << 28) >> 28
        hi = q >> 4
        q = jnp.stack([lo, hi], axis=1).reshape(q.shape[0] * 2, q.shape[1])
    return q.astype(jnp.float32) * s_ref[0].astype(jnp.float32)


def _wq_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int, scale: float,
               act: str | None, out_dtype, pack: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # in-tile dequant: the weight tile crossed HBM at storage width
    w = _dequant_tile(q_ref, s_ref, pack)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...]
        if scale != 1.0:
            out = out * scale
        if act == "gelu":
            out = jax.nn.gelu(out, approximate=True)
        elif act == "silu":
            out = jax.nn.silu(out)
        o_ref[...] = out.astype(out_dtype)


def _wq_bias_kernel(x_ref, q_ref, s_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                    scale: float, act: str | None, out_dtype, pack: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = _dequant_tile(q_ref, s_ref, pack)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        out = acc_ref[...] * scale + b_ref[...].astype(jnp.float32)
        if act == "gelu":
            out = jax.nn.gelu(out, approximate=True)
        elif act == "silu":
            out = jax.nn.silu(out)
        o_ref[...] = out.astype(out_dtype)


def gemm_wq(x, qw, tile_scales, *, bias=None, scale: float = 1.0,
            act: str | None = None, block_m: int = 128, block_n: int = 128,
            block_k: int = 128, out_dtype=jnp.float32,
            interpret: bool = False, pack: int = 1):
    """x: (M, K) float @ qw: (K/pack, N) int8/fp8 -> (M, N), fused epilogue.

    ``tile_scales``: (K // block_k, N) fp32 — one dequant-scale row per
    K-tile (the wrapper expands per-block scales; a tile never straddles a
    quant block). ``pack=2`` marks int4 nibble-packed ``qw`` (unpacked
    in-tile). Shapes must already be padded to the block multiples; block
    sizes are in *logical* K elements, so ``block_k % pack == 0``.
    """
    M, K = x.shape
    Kq, N = qw.shape
    assert Kq * pack == K, (x.shape, qw.shape, pack)
    assert block_k % pack == 0, (block_k, pack)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "pad in ops.py first", (M, K, N), (block_m, block_k, block_n))
    n_k = K // block_k
    assert tile_scales.shape == (n_k, N), (tile_scales.shape, n_k, N)
    grid = (M // block_m, N // block_n, n_k)
    bkq = block_k // pack          # storage rows per weight tile
    # scale rows ride as (n_k, 1, N) so the block's last two dims (1, bn)
    # match the array's (1, N) row, as the TPU lowering requires
    tile_scales = tile_scales.reshape(n_k, 1, N)
    scale_spec = pl.BlockSpec((1, 1, block_n), lambda i, j, k: (k, 0, j))

    if bias is None:
        kernel = functools.partial(_wq_kernel, n_k=n_k, scale=scale, act=act,
                                   out_dtype=out_dtype, pack=pack)
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkq, block_n), lambda i, j, k: (k, j)),
            scale_spec,
        ]
        args = (x, qw, tile_scales)
    else:
        kernel = functools.partial(_wq_bias_kernel, n_k=n_k, scale=scale,
                                   act=act, out_dtype=out_dtype, pack=pack)
        in_specs = [
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((bkq, block_n), lambda i, j, k: (k, j)),
            scale_spec,
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ]
        args = (x, qw, tile_scales, bias.reshape(1, N))

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(*args)
