"""Packed irregular streams (paper C5c) / SU indirect streams (C2) on TPU.

The paper's streaming units issue index-driven accesses that the Ogopogo
extension packs into wide NoC flits with an HBM-side coalescer. The TPU
analogue: a *scalar-prefetched* index array drives the ``BlockSpec``
``index_map`` — the indices arrive ahead of the data (exactly an SU's index
FIFO) and each grid step DMAs ``pack`` table rows as one wide, lane-aligned
VMEM tile. The ops.py wrapper optionally sorts indices first (the temporal
coalescer), turning random narrow reads into near-sequential wide ones.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_kernel(idx_ref, table_ref, o_ref):
    # the whole block was DMA'd by the index_map; plain copy through VMEM
    o_ref[...] = table_ref[...]


def gather_rows(table, idx, *, interpret: bool = False):
    """out[i] = table[idx[i]]  — one row per grid step, index-driven DMA.

    table: (N, D); idx: (M,) int32. The narrow-stream baseline (8 B–wide
    requests in the paper; one D-row here). Rows ride as ``(N, 1, D)`` so a
    ``(1, 1, D)`` block spans the array's last two dims, as the TPU lowering
    requires of a one-row block.
    """
    N, D = table.shape
    M = idx.shape[0]
    row = pl.BlockSpec((1, 1, D), lambda i, idx_ref: (idx_ref[i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M,),
        in_specs=[row],
        out_specs=pl.BlockSpec((1, 1, D), lambda i, idx_ref: (i, 0, 0)),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, 1, D), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), table.reshape(N, 1, D)).reshape(M, D)


def _packed_kernel(idx_ref, table_ref, o_ref, *, pack: int, window: int):
    # gather `pack` rows from the VMEM-resident window into one wide tile
    i = pl.program_id(0)
    base = (idx_ref[i * pack] // window) * window  # staged window start
    for r in range(pack):
        src = idx_ref[i * pack + r] - base         # offset within window
        o_ref[r, :] = table_ref[src, :]


def packed_gather_rows(table, idx, *, pack: int = 8, window: int = 256,
                       interpret: bool = False):
    """Packed variant: ``pack`` indexed rows per grid step, fetched from a
    ``window``-row table tile staged in VMEM (the wide-flit + coalescer pair).
    Requires indices pre-sorted (ops.py does this) so each pack's rows fall
    within one window: idx[i*pack+r] - idx[i*pack] < window.
    """
    N, D = table.shape
    M = idx.shape[0]
    assert M % pack == 0, "pad in ops.py first"
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(M // pack,),
        in_specs=[pl.BlockSpec(
            (window, D),
            # stage the window containing this pack's first row
            lambda i, idx_ref: (idx_ref[i * pack] // window, 0))],
        out_specs=pl.BlockSpec((pack, D), lambda i, idx_ref: (i, 0)),
    )
    kernel = functools.partial(_packed_kernel, pack=pack, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, D), table.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), table)
