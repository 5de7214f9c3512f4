"""Structured-sparse GEMM kernels (Pallas TPU): block-sparse and 2:4.

The Occamy stencil/sparse companion (arXiv:2406.15068) holds 42-83% FPU
utilization on SpMM/STC by keeping the *structure* of the sparsity coarse
enough that the FPU still streams dense inner tiles. Both kernels here
follow that recipe — sparsity lives at a granularity the MXU can exploit,
never per-scalar:

* **block-sparse** — a ``(K/bs_k, N/bs_n)`` boolean block mask gates whole
  ``(bs_k, bs_n)`` weight tiles. The kernel keeps the dense gemm schedule
  (grid ``(M/bm, N/bn, K/bk)``, K innermost, VMEM fp32 accumulator) and
  skips the MXU issue for masked tiles via ``pl.when`` — zero blocks cost
  one scalar-prefetched SMEM mask read instead of a (bk, bn) FLOP tile.
* **2:4 fine-grained** — every group of 4 consecutive K elements keeps its
  2 largest-magnitude values. Storage is ``(K/2, N)`` values + ``(K/2, N)``
  int8 column-local indices; the kernel densifies in-tile with an
  iota-compare scatter (the same trick sparse tensor cores implement in
  silicon) and runs a dense (bk, bn) MXU tile — HBM traffic halves, the
  in-register FLOPs stay dense.

The dense-mask ref oracle lives in ref.py (``gemm_sparse_ref``): it
materializes the masked/densified weight and calls the plain jnp GEMM, so
kernel-vs-ref parity is *exact* (same reassociation per output element).

Helpers (:func:`block_mask_from_weight`, :func:`apply_block_mask`,
:func:`sparsify_24`, :func:`densify_24`) are the pruning front-end shared
by the MoE consumer (models/moe.py) and the benchmarks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# --------------------------------------------------------------------------
# pruning helpers (host-side front-end, plain jnp)
# --------------------------------------------------------------------------
def block_mask_from_weight(w, bs_k: int, bs_n: int, density: float):
    """Magnitude prune ``w: (K, N)`` to a ``(K/bs_k, N/bs_n)`` bool block
    mask keeping the ``density`` fraction of blocks with largest L2 norm."""
    K, N = w.shape
    if K % bs_k or N % bs_n:
        raise ValueError(f"block {bs_k}x{bs_n} must tile {w.shape}")
    kb, nb = K // bs_k, N // bs_n
    norms = (w.astype(jnp.float32) ** 2).reshape(
        kb, bs_k, nb, bs_n).sum(axis=(1, 3))
    n_keep = max(1, min(kb * nb, round(density * kb * nb)))
    thresh = jnp.sort(norms.reshape(-1))[kb * nb - n_keep]
    return norms >= thresh


def apply_block_mask(w, mask):
    """Zero the masked-out blocks of ``w`` (the dense oracle's weight)."""
    K, N = w.shape
    kb, nb = mask.shape
    bs_k, bs_n = K // kb, N // nb
    wm = w.reshape(kb, bs_k, nb, bs_n) * mask[:, None, :, None].astype(
        w.dtype)
    return wm.reshape(K, N)


def sparsify_24(w):
    """2:4 magnitude prune ``w: (K, N)`` (K % 4 == 0): per group of 4
    consecutive K rows keep the 2 largest-|w|. Returns ``(vals (K/2, N),
    idx (K/2, N) int8)`` with in-group positions 0..3, ascending per pair."""
    K, N = w.shape
    if K % 4:
        raise ValueError(f"2:4 needs K % 4 == 0, got K={K}")
    g = w.reshape(K // 4, 4, N)
    order = jnp.argsort(-jnp.abs(g.astype(jnp.float32)), axis=1)[:, :2, :]
    idx = jnp.sort(order, axis=1)                      # deterministic layout
    vals = jnp.take_along_axis(g, idx, axis=1)
    return (vals.reshape(K // 2, N).astype(w.dtype),
            idx.reshape(K // 2, N).astype(jnp.int8))


def densify_24(vals, idx):
    """Scatter 2:4 storage back to the dense ``(K, N)`` weight (zeros at
    pruned positions) — the ref oracle's weight and the iota-compare
    pattern the kernel runs per tile."""
    Kh, N = vals.shape
    v = vals.astype(jnp.float32).reshape(Kh // 2, 2, N)
    i = idx.astype(jnp.int32).reshape(Kh // 2, 2, N)
    iota = jax.lax.broadcasted_iota(jnp.int32, (Kh // 2, 4, N), 1)
    dense = ((iota == i[:, 0:1]) * v[:, 0:1]
             + (iota == i[:, 1:2]) * v[:, 1:2])
    return dense.reshape(Kh * 2, N)


def _epilogue(out, scale, act, out_dtype):
    if scale != 1.0:
        out = out * scale
    if act == "gelu":
        out = jax.nn.gelu(out, approximate=True)
    elif act == "silu":
        out = jax.nn.silu(out)
    return out.astype(out_dtype)


# --------------------------------------------------------------------------
# block-sparse kernel
# --------------------------------------------------------------------------
def _bs_kernel(m_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int, nb: int,
               rk: int, rn: int, scale: float, act: str | None, out_dtype):
    j = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the flattened block mask rides in SMEM (scalar prefetch); one entry
    # gates the whole MXU issue for this K step
    @pl.when(m_ref[(k // rk) * nb + j // rn] != 0)
    def _accum():
        acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                                w_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fin():
        o_ref[...] = _epilogue(acc_ref[...], scale, act, out_dtype)


def gemm_sparse(x, w, mask, *, scale: float = 1.0, act: str | None = None,
                block_m: int = 128, block_n: int = 128, block_k: int = 128,
                out_dtype=jnp.float32, interpret: bool = False):
    """x: (M, K) @ block-masked w: (K, N) -> (M, N); mask (K/bs_k, N/bs_n)
    bool/int gates whole weight blocks. Kernel tile sizes must divide the
    mask block sizes (the wrapper shrinks them via gcd); shapes must be
    pre-padded to the block multiples."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    kb, nb = mask.shape
    bs_k, bs_n = K // kb, N // nb
    assert bs_k % block_k == 0 and bs_n % block_n == 0, (
        "kernel tiles must divide mask blocks", (bs_k, bs_n),
        (block_k, block_n))
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "pad in ops.py first", (M, K, N), (block_m, block_k, block_n))
    n_k = K // block_k
    grid = (M // block_m, N // block_n, n_k)
    rk, rn = bs_k // block_k, bs_n // block_n     # kernel tiles per block

    kernel = functools.partial(_bs_kernel, n_k=n_k, nb=nb, rk=rk, rn=rn,
                               scale=scale, act=act, out_dtype=out_dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                       # flattened block mask
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k, m: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k, m: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, k, m: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
    )(mask.astype(jnp.int32).reshape(-1), x, w)


# --------------------------------------------------------------------------
# 2:4 fine-grained kernel
# --------------------------------------------------------------------------
def _s24_kernel(x_ref, v_ref, i_ref, o_ref, acc_ref, *, n_k: int,
                scale: float, act: str | None, out_dtype):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # in-tile densify: (bk/2, bn) vals+idx crossed HBM at half the dense
    # bytes; the iota-compare scatter rebuilds the (bk, bn) dense tile in
    # VMEM (what a sparse tensor core does in its operand mux)
    bk2, bn = v_ref.shape
    v = v_ref[...].astype(jnp.float32).reshape(bk2 // 2, 2, bn)
    i = i_ref[...].astype(jnp.int32).reshape(bk2 // 2, 2, bn)
    iota = jax.lax.broadcasted_iota(jnp.int32, (bk2 // 2, 4, bn), 1)
    w = ((iota == i[:, 0:1]) * v[:, 0:1]
         + (iota == i[:, 1:2]) * v[:, 1:2]).reshape(bk2 * 2, bn)
    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _fin():
        o_ref[...] = _epilogue(acc_ref[...], scale, act, out_dtype)


def gemm_sparse_24(x, vals, idx, *, scale: float = 1.0,
                   act: str | None = None, block_m: int = 128,
                   block_n: int = 128, block_k: int = 128,
                   out_dtype=jnp.float32, interpret: bool = False):
    """x: (M, K) @ 2:4-compressed w -> (M, N). ``vals``/``idx``: (K/2, N)
    from :func:`sparsify_24`. ``block_k`` counts logical K elements and
    must be a multiple of 4; shapes pre-padded to the block multiples."""
    M, K = x.shape
    Kh, N = vals.shape
    assert Kh * 2 == K, (x.shape, vals.shape)
    assert idx.shape == vals.shape, (idx.shape, vals.shape)
    assert block_k % 4 == 0, block_k
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        "pad in ops.py first", (M, K, N), (block_m, block_k, block_n))
    n_k = K // block_k
    grid = (M // block_m, N // block_n, n_k)

    kernel = functools.partial(_s24_kernel, n_k=n_k, scale=scale, act=act,
                               out_dtype=out_dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k // 2, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(x, vals, idx)
