"""Public kernel ops, dispatched through the backend registry.

Each op here is a *name* in :data:`repro.kernels.dispatch.registry`. The
Pallas entry pads its operands to kernel-aligned tiles (sizes negotiated from
the per-op tuning table), runs the kernel (compiled on TPU, interpreted for
CPU validation), and unpads only when padding actually happened; the pure-jnp
oracle in :mod:`repro.kernels.ref` is registered alongside it as the universal
fallback. There are no ``impl=`` switches — select a backend with
``dispatch.use_backend(...)`` (or let platform auto-detection pick), and
requests a kernel can't serve (GQA head counts outside the kernel layout,
sub-lane head dims, integer dtypes) negotiate down to the oracle instead of
erroring. See docs/backends.md.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.dispatch import (OpRequest, registry, serve_mesh,
                                    use_backend)
from repro.kernels.flash_attention import flash_attention as _fa
from repro.kernels.gemm import gemm as _gemm
from repro.kernels.gemm_sparse import gemm_sparse as _gemm_sparse
from repro.kernels.gemm_sparse import gemm_sparse_24 as _gemm_sparse_24
from repro.kernels.gemm_wq import gemm_wq as _gemm_wq
from repro.kernels.instream import instream_scale_reduce as _instream
from repro.kernels.lru_scan import lru_scan as _lru
from repro.kernels.packed_gather import gather_rows as _gather
from repro.kernels.packed_gather import packed_gather_rows as _packed_gather
from repro.kernels.paged_attention import paged_attention as _pa
from repro.kernels.paged_mla import paged_mla as _pmla

__all__ = ["flash_attention", "gather_rows", "gemm", "gemm_sparse",
           "gemm_sparse_24", "gemm_wq", "instream_scale_reduce", "lru_scan",
           "packed_gather_rows", "paged_attention", "paged_mla", "registry",
           "use_backend"]

#: Storage dtype names of quantized weight/KV operands (str(jnp.dtype)) —
#: the quant subsystem's canonical list, not a private copy.
from repro.quant import QUANT_DTYPES as _QUANT_DTYPES  # noqa: E402


def _is_float(d: str) -> bool:
    """True for *dense* float dtypes (fp8 storage dtypes excluded)."""
    return (("float" in d) or ("bf16" in d)) and d not in _QUANT_DTYPES


def _pad_to(x, mults, axes):
    pads = [(0, 0)] * x.ndim
    padded = False
    for ax, m in zip(axes, mults):
        r = (-x.shape[ax]) % m
        if r:
            pads[ax] = (0, r)
            padded = True
    return (jnp.pad(x, pads), True) if padded else (x, False)


# --------------------------------------------------------------------------
# gemm — streaming tiled GEMM with fused epilogue (paper C1 + C5b)
# --------------------------------------------------------------------------
def _gemm_supports(req: OpRequest) -> bool:
    return (len(req.shapes) >= 2 and all(len(s) == 2 for s in req.shapes[:2])
            and req.floating())


@registry.register("gemm", "pallas", backends=("pallas", "interpret"),
                   supports=_gemm_supports, priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("scale", "act", "block_m", "block_n",
                                   "block_k", "interpret"))
def _gemm_kernel(x, w, bias=None, *, scale: float = 1.0, act: str | None = None,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128,
                 interpret: bool = False):
    M, K = x.shape
    N = w.shape[1]
    xp, px = _pad_to(x, (block_m, block_k), (0, 1))
    wp, pw = _pad_to(w, (block_k, block_n), (0, 1))
    bp = None
    if bias is not None:
        bp, _ = _pad_to(bias, (block_n,), (0,))
    out = _gemm(xp, wp, bias=bp, scale=scale, act=act, block_m=block_m,
                block_n=block_n, block_k=block_k, interpret=interpret)
    return out[:M, :N] if (px or pw) else out


@registry.register("gemm", "ref", backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("scale", "act"))
def _gemm_ref(x, w, bias=None, *, scale: float = 1.0, act: str | None = None):
    return _ref.gemm_ref(x, w, bias=bias, scale=scale, act=act)


registry.register_blocks("gemm", "small", block_m=32, block_n=32, block_k=32)
registry.register_blocks("gemm", "large", block_m=128, block_n=128,
                         block_k=128)


def gemm(x, w, bias=None, *, scale: float = 1.0, act: str | None = None,
         **blocks):
    """x: (M, K) @ w: (K, N) with fused scale/bias/activation epilogue.

    Tile sizes come from the tuning table; pass ``block_m``/``block_n``/
    ``block_k`` to pin them for this call.
    """
    return registry.dispatch("gemm", x, w, bias, scale=scale, act=act,
                             **blocks)


# --------------------------------------------------------------------------
# gemm_wq — weight-quantized GEMM, dequantized in-tile (paper Fig. 4b:
# halving precision doubles density; weights stream HBM at storage width)
# --------------------------------------------------------------------------
def _gemm_wq_supports(req: OpRequest) -> bool:
    if len(req.shapes) < 3 or any(len(s) != 2 for s in req.shapes[:3]):
        return False
    (M, K), (K2, N), (nb, N2) = req.shapes[:3]
    if not (N == N2 and nb >= 1 and K % nb == 0
            and _is_float(req.dtypes[0])):
        return False
    if K == K2:
        return req.dtypes[1] in _QUANT_DTYPES
    # nibble-packed int4: the weight's K axis is physically halved, and a
    # quant block must hold a whole number of bytes so K-tiles stay packed
    return (K2 * 2 == K and req.dtypes[1] == "int8"
            and (K // nb) % 2 == 0)


@registry.register("gemm_wq", "pallas", backends=("pallas", "interpret"),
                   supports=_gemm_wq_supports, priority=10,
                   pass_interpret=True)
@partial(jax.jit, static_argnames=("scale", "act", "block_m", "block_n",
                                   "block_k", "interpret"))
def _gemm_wq_kernel(x, qw, scales, bias=None, *, scale: float = 1.0,
                    act: str | None = None, block_m: int = 128,
                    block_n: int = 128, block_k: int = 128,
                    interpret: bool = False):
    import math

    M, K = x.shape                     # logical K (int4: qw rows are K/2)
    N = qw.shape[1]
    pack = 2 if qw.shape[0] * 2 == K else 1
    nb = scales.shape[0]
    qb = K // nb                       # quant-block length along K
    # a K-tile must never straddle a quant block: largest block_k-compatible
    # divisor of qb (K % bk == 0 follows since bk | qb | K — no K padding)
    bk = math.gcd(block_k, qb)
    if pack == 2 and bk % 2:
        # packed tiles hold whole bytes; qb is even (supports()), so this
        # stays a divisor of qb
        bk = math.gcd(2 * bk, qb)
    n_k = K // bk
    # one dequant-scale row per K-tile, pre-gathered so the kernel's scale
    # BlockSpec is a plain (k, j) index map
    tile_scales = scales.astype(jnp.float32)[
        (jnp.arange(n_k) * bk) // qb]
    xp, px = _pad_to(x, (block_m,), (0,))
    qp, pw = _pad_to(qw, (block_n,), (1,))
    sp, _ = _pad_to(tile_scales, (block_n,), (1,))
    bp = None
    if bias is not None:
        bp, _ = _pad_to(bias, (block_n,), (0,))
    out = _gemm_wq(xp, qp, sp, bias=bp, scale=scale, act=act,
                   block_m=block_m, block_n=block_n, block_k=bk,
                   interpret=interpret, pack=pack)
    return out[:M, :N] if (px or pw) else out


@registry.register("gemm_wq", "ref", backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("scale", "act"))
def _gemm_wq_ref(x, qw, scales, bias=None, *, scale: float = 1.0,
                 act: str | None = None):
    return _ref.gemm_wq_ref(x, qw, scales, bias=bias, scale=scale, act=act)


registry.register_blocks("gemm_wq", "small", block_m=32, block_n=32,
                         block_k=32)
registry.register_blocks("gemm_wq", "large", block_m=128, block_n=128,
                         block_k=128)


def gemm_wq(x, qw, scales, bias=None, *, scale: float = 1.0,
            act: str | None = None, **blocks):
    """Weight-quantized x: (M, K) @ qw: (K, N) int8/fp8 — or (K/2, N) int8
    nibble-packed int4, recognized by the half-K shape relation — with
    per-block dequant scales (nb, N), nb | K (nb == 1 => per-channel), and
    the same fused scale/bias/activation epilogue as ``gemm``.

    The Pallas entry dequantizes (int4: unpacks, then dequantizes) weight
    tiles in-register after the DMA; requests the kernel layout can't
    express (odd ranks, dense-float weights, odd-byte quant blocks)
    negotiate down to the dequantize-then-``gemm`` oracle.
    """
    return registry.dispatch("gemm_wq", x, qw, scales, bias, scale=scale,
                             act=act, **blocks)


# --------------------------------------------------------------------------
# gemm_sparse — structured-sparse GEMM (paper's SpMM/STC arc, arXiv:2406.15068:
# sparsity coarse enough that the FPU still streams dense inner tiles)
# --------------------------------------------------------------------------
def _gemm_sparse_block_supports(req: OpRequest) -> bool:
    """Block-sparse layout: (M, K) x, (K, N) float w, (K/bs_k, N/bs_n)
    bool/int block mask."""
    if len(req.shapes) < 3 or any(len(s) != 2 for s in req.shapes[:3]):
        return False
    (M, K), (K2, N), (kb, nb) = req.shapes[:3]
    return (K == K2 and kb >= 1 and nb >= 1 and K % kb == 0 and N % nb == 0
            and _is_float(req.dtypes[0]) and _is_float(req.dtypes[1])
            and ("bool" in req.dtypes[2] or "int" in req.dtypes[2]))


def _gemm_sparse_24_supports(req: OpRequest) -> bool:
    """2:4 layout: (M, K) x, (K/2, N) float vals, (K/2, N) int8 indices."""
    if len(req.shapes) < 3 or any(len(s) != 2 for s in req.shapes[:3]):
        return False
    (M, K), (Kh, N), idx_shape = req.shapes[:3]
    return (Kh * 2 == K and K % 4 == 0 and idx_shape == (Kh, N)
            and _is_float(req.dtypes[0]) and _is_float(req.dtypes[1])
            and req.dtypes[2] == "int8")


@registry.register("gemm_sparse", "pallas_block",
                   backends=("pallas", "interpret"),
                   supports=_gemm_sparse_block_supports, priority=10,
                   pass_interpret=True)
@partial(jax.jit, static_argnames=("scale", "act", "block_m", "block_n",
                                   "block_k", "interpret"))
def _gemm_sparse_block_kernel(x, w, mask, *, scale: float = 1.0,
                              act: str | None = None, block_m: int = 128,
                              block_n: int = 128, block_k: int = 128,
                              interpret: bool = False):
    import math

    M, K = x.shape
    N = w.shape[1]
    kb, nb = mask.shape
    bs_k, bs_n = K // kb, N // nb
    # kernel tiles must divide the mask blocks (and the mask blocks divide
    # K/N), so shrinking via gcd removes any need for K/N padding
    bk = math.gcd(block_k, bs_k)
    bn = math.gcd(block_n, bs_n)
    xp, px = _pad_to(x, (block_m,), (0,))
    out = _gemm_sparse(xp, w, mask, scale=scale, act=act, block_m=block_m,
                       block_n=bn, block_k=bk, interpret=interpret)
    return out[:M] if px else out


@registry.register("gemm_sparse", "pallas_24",
                   backends=("pallas", "interpret"),
                   supports=_gemm_sparse_24_supports, priority=10,
                   pass_interpret=True)
@partial(jax.jit, static_argnames=("scale", "act", "block_m", "block_n",
                                   "block_k", "interpret"))
def _gemm_sparse_24_kernel(x, vals, idx, *, scale: float = 1.0,
                           act: str | None = None, block_m: int = 128,
                           block_n: int = 128, block_k: int = 128,
                           interpret: bool = False):
    import math

    M, K = x.shape
    N = vals.shape[1]
    bk = math.gcd(block_k, K)
    if bk % 4:                         # tiles hold whole 2:4 groups
        bk = math.gcd(4 * bk, K)
    xp, px = _pad_to(x, (block_m,), (0,))
    vp, pn = _pad_to(vals, (block_n,), (1,))
    # zero-padded idx columns pair zero vals: the densified tile stays zero
    ip, _ = _pad_to(idx, (block_n,), (1,))
    out = _gemm_sparse_24(xp, vp, ip, scale=scale, act=act, block_m=block_m,
                          block_n=block_n, block_k=bk, interpret=interpret)
    return out[:M, :N] if (px or pn) else out


@registry.register("gemm_sparse", "ref",
                   backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("scale", "act"))
def _gemm_sparse_ref(x, w_or_vals, mask_or_idx, *, scale: float = 1.0,
                     act: str | None = None):
    return _ref.gemm_sparse_ref(x, w_or_vals, mask_or_idx, scale=scale,
                                act=act)


registry.register_blocks("gemm_sparse", "small", block_m=32, block_n=32,
                         block_k=32)
registry.register_blocks("gemm_sparse", "large", block_m=128, block_n=128,
                         block_k=128)


def gemm_sparse(x, w, mask, *, scale: float = 1.0, act: str | None = None,
                **blocks):
    """Block-sparse x: (M, K) @ w: (K, N) gated by a (K/bs_k, N/bs_n)
    bool/int block mask: masked weight blocks are skipped — no MXU issue,
    no FLOPs — and the epilogue matches ``gemm``. Layouts the kernel can't
    express negotiate down to the dense-mask oracle (exact parity: the
    oracle zeroes the same blocks and runs the plain GEMM)."""
    return registry.dispatch("gemm_sparse", x, w, mask, scale=scale,
                             act=act, **blocks)


def gemm_sparse_24(x, vals, idx, *, scale: float = 1.0,
                   act: str | None = None, **blocks):
    """2:4 fine-grained sparse GEMM: ``vals``/``idx`` (K/2, N) from
    ``gemm_sparse.sparsify_24`` — 2 survivors per 4 consecutive K elements.
    Weight HBM traffic halves; the kernel densifies in-tile (iota-compare
    scatter) and runs dense MXU tiles. Same op name as ``gemm_sparse``:
    the registry picks the layout by operand shapes/dtypes."""
    return registry.dispatch("gemm_sparse", x, vals, idx, scale=scale,
                             act=act, **blocks)


# --------------------------------------------------------------------------
# flash_attention — FlashAttention-2 schedule (paper §II-C)
# --------------------------------------------------------------------------
def _fa_supports(req: OpRequest) -> bool:
    if len(req.shapes) < 3 or any(len(s) != 3 for s in req.shapes[:3]):
        return False
    (BH, _, D), (BK, _, _) = req.shapes[0], req.shapes[1]
    # kernel layout: kv tiles shared across each GQA group (BH = BK*G), and
    # the head dim must fill at least one sublane — else negotiate to ref
    return BH % BK == 0 and D >= 8 and req.floating()


@registry.register("flash_attention", "pallas",
                   backends=("pallas", "interpret"), supports=_fa_supports,
                   priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("causal", "window", "cap", "scale",
                                   "block_q", "block_k", "interpret"))
def _fa_kernel(q, k, v, *, causal: bool = True, window: int = 0,
               cap: float = 0.0, scale: float | None = None,
               block_q: int = 128, block_k: int = 128,
               interpret: bool = False):
    Sq = q.shape[1]
    Skv = k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    qp, pq = _pad_to(q, (bq,), (1,))
    kp, _ = _pad_to(k, (bk,), (1,))
    vp, _ = _pad_to(v, (bk,), (1,))
    out = _fa(qp, kp, vp, causal=causal, window=window, cap=cap, scale=scale,
              kv_len=Skv, block_q=bq, block_k=bk, interpret=interpret)
    return out[:, :Sq] if pq else out


@registry.register("flash_attention", "ref",
                   backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("causal", "window", "cap", "scale"))
def _fa_ref(q, k, v, *, causal: bool = True, window: int = 0, cap: float = 0.0,
            scale: float | None = None):
    # ref.flash_attention_ref handles GQA with a grouped reshape — no
    # jnp.repeat'd K/V materialization at high group counts
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                    cap=cap, scale=scale)


registry.register_blocks("flash_attention", "small", block_q=32, block_k=32)
registry.register_blocks("flash_attention", "large", block_q=128, block_k=128)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    cap: float = 0.0, scale: float | None = None, **blocks):
    """q: (BH, Sq, D); k, v: (BK, Skv, D) with BH % BK == 0 (GQA groups).

    Head counts or dims outside the kernel layout negotiate down to the
    grouped oracle. ``block_q``/``block_k`` pin tile sizes for this call.
    """
    return registry.dispatch("flash_attention", q, k, v, causal=causal,
                             window=window, cap=cap, scale=scale, **blocks)


# --------------------------------------------------------------------------
# paged_attention — block-pool KV decode attention (serving)
# --------------------------------------------------------------------------
def _pa_operands_ok(req: OpRequest) -> bool:
    """Whether a paged-attention request's operands have the op's layout:
    q ``(B, K, G, D)``, stacked pools ``(L, N, page, K, D)`` with q's heads
    and dims, int32 tables, lengths and a scalar layer, and for quantized
    pools two ``(L, N, page, K)`` scale operands."""
    if len(req.shapes) < 6 or len(req.shapes[0]) != 4:
        return False
    (B, K, G, D) = req.shapes[0]
    if any(len(s) != 5 or s[3:] != (K, D) for s in req.shapes[1:3]):
        return False
    if req.shapes[5] != () or not all("int" in d for d in req.dtypes[3:6]):
        return False
    return len(req.shapes) < 8 or (req.shapes[6] == req.shapes[1][:4]
                                   == req.shapes[7])


def _pa_supports(req: OpRequest) -> bool:
    # the head dim must fill at least one sublane, else negotiate down to
    # the gather oracle
    if not _pa_operands_ok(req) or req.shapes[0][3] < 8:
        return False
    if not _is_float(req.dtypes[0]):
        return False
    if len(req.shapes) >= 8:
        # quantized pools: int8/fp8 storage + per-row float scales
        return (all(d in _QUANT_DTYPES for d in req.dtypes[1:3])
                and all(_is_float(d) for d in req.dtypes[6:8]))
    return all(_is_float(d) for d in req.dtypes[1:3])


@registry.register("paged_attention", "pallas",
                   backends=("pallas", "interpret"), supports=_pa_supports,
                   priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("scale", "cap", "interpret"))
def _pa_kernel(q, k_pool, v_pool, block_tables, lengths, layer, k_scale=None,
               v_scale=None, *, scale: float | None = None, cap: float = 0.0,
               interpret: bool = False):
    return _pa(q, k_pool, v_pool, block_tables, lengths, layer, k_scale,
               v_scale, scale=scale, cap=cap, interpret=interpret)


@registry.register("paged_attention", "ref",
                   backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("scale", "cap"))
def _pa_ref(q, k_pool, v_pool, block_tables, lengths, layer, k_scale=None,
            v_scale=None, *, scale: float | None = None, cap: float = 0.0):
    return _ref.paged_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                                    layer, k_scale, v_scale, scale=scale,
                                    cap=cap)


def _pa_sharded_supports(req: OpRequest) -> bool:
    """Sharded layout negotiation: only inside a ``serve_mesh_scope`` (the
    model layer advertising KV-head-sharded pools), and only when the KV
    head count divides the mesh axis — otherwise the pools were replicated
    by the divisibility-drop rule and the local paths serve unchanged."""
    sm = serve_mesh()
    if sm is None or not _pa_operands_ok(req):
        return False
    mesh, axis = sm
    n = mesh.shape.get(axis, 1)
    return n > 1 and req.shapes[0][1] % n == 0


@registry.register("paged_attention", "sharded",
                   backends=("ref", "interpret", "pallas"),
                   supports=_pa_sharded_supports, priority=20)
def _pa_sharded(q, k_pool, v_pool, block_tables, lengths, layer, k_scale=None,
                v_scale=None, *, scale: float | None = None,
                cap: float = 0.0):
    from repro.kernels.paged_attention import paged_attention_sharded
    mesh, axis = serve_mesh()
    return paged_attention_sharded(q, k_pool, v_pool, block_tables, lengths,
                                   layer, k_scale, v_scale, mesh=mesh,
                                   axis=axis, scale=scale, cap=cap)


def paged_attention(q, k_pool, v_pool, block_tables, lengths, layer,
                    k_scale=None, v_scale=None, *, scale: float | None = None,
                    cap: float = 0.0, **blocks):
    """Block-pool decode attention. q: (B, K, G, D) one token per slot;
    k/v pools stacked over layers: (L, N, page, K, D), read at ``layer``
    (int or int32 scalar, may be traced — the layer scan passes its index,
    so no per-layer slice of the pool is made); block_tables: (B, P) int32;
    lengths: (B,) int32 valid tokens per slot. A single layer's
    (N, page, K, D) pool passes as ``pool[None]`` with layer 0.
    ``k_scale``/``v_scale`` ((L, N, page, K) float) mark quantized
    (int8/fp8) pools — rows dequantize at read with their per-row absmax
    scales. Pool layouts the kernel can't express negotiate down to the
    gather-based oracle."""
    if str(k_pool.dtype) in _QUANT_DTYPES and k_scale is None:
        # negotiation falls back to *correct* paths only: attention over
        # raw int8/fp8 codes would be silent garbage, not a fallback
        raise ValueError(
            f"paged_attention: quantized pools ({k_pool.dtype}) require "
            "k_scale/v_scale per-row dequant scales")
    return registry.dispatch("paged_attention", q, k_pool, v_pool,
                             block_tables, lengths,
                             jnp.asarray(layer, jnp.int32), k_scale, v_scale,
                             scale=scale, cap=cap, **blocks)


# --------------------------------------------------------------------------
# paged_mla — absorbed latent-attention decode over a paged latent pool
# --------------------------------------------------------------------------
def _mla_operands_ok(req: OpRequest) -> bool:
    """q ``(B, H, D)``, a stacked latent pool ``(L, N, page, D)`` of q's
    width, int32 tables and lengths, and a scalar layer."""
    if len(req.shapes) != 5 or len(req.shapes[0]) != 3:
        return False
    D = req.shapes[0][2]
    return (len(req.shapes[1]) == 4 and req.shapes[1][3] == D
            and req.shapes[4] == ()
            and all("int" in d for d in req.dtypes[2:5]))


def _mla_supports(req: OpRequest) -> bool:
    # the kernel copies whole rows by DMA: the row must fill whole lane
    # tiles (the HBM tiling pads a narrower minor dim, and a DMA slice must
    # be tile-aligned), and so must the value columns
    if not _mla_operands_ok(req):
        return False
    D, dv = req.shapes[0][2], req.param("dv", 0)
    return (D % 128 == 0 and dv % 128 == 0 and 0 < dv <= D
            and all(_is_float(d) for d in req.dtypes[:2]))


@registry.register("paged_mla", "pallas", backends=("pallas", "interpret"),
                   supports=_mla_supports, priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("dv", "pages_per_step", "interpret"))
def _mla_kernel(q, pool, block_tables, lengths, layer, *, dv: int,
                pages_per_step: int = 32, interpret: bool = False):
    return _pmla(q, pool, block_tables, lengths, layer, dv=dv,
                 pages_per_step=pages_per_step, interpret=interpret)


@registry.register("paged_mla", "ref", backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("dv",))
def _mla_ref(q, pool, block_tables, lengths, layer, *, dv: int):
    return _ref.paged_mla_ref(q, pool, block_tables, lengths, layer, dv=dv)


registry.register_blocks("paged_mla", "*", pages_per_step=32)


def paged_mla(q, pool, block_tables, lengths, layer, *, dv: int, **blocks):
    """Absorbed multi-head latent attention decode (DeepSeek-V2). q:
    (B, H, D) one pre-scaled absorbed query per slot and head (``[q_nope
    W_UK, q_pe]``, zero past the row's used columns); pool: latent rows
    stacked over layers (L, N, page, D), read at ``layer`` (int or int32
    scalar, may be traced); block_tables: (B, P) int32; lengths: (B,)
    int32 rows per slot. Returns (B, H, dv): the softmax over the slot's
    rows of ``q · row``, applied to each row's first ``dv`` columns (the
    latent). Pool layouts the kernel cannot copy negotiate down to the
    gather-based oracle."""
    if str(pool.dtype) in _QUANT_DTYPES:
        raise ValueError(
            f"paged_mla: quantized latent pools ({pool.dtype}) are not "
            "supported")
    return registry.dispatch("paged_mla", q, pool, block_tables, lengths,
                             jnp.asarray(layer, jnp.int32), dv=dv, **blocks)


# --------------------------------------------------------------------------
# lru_scan — diagonal linear recurrence (RG-LRU / Mamba foundation)
# --------------------------------------------------------------------------
def _lru_supports(req: OpRequest) -> bool:
    return (len(req.shapes) >= 2 and all(len(s) == 3 for s in req.shapes[:2])
            and req.floating())


@registry.register("lru_scan", "pallas", backends=("pallas", "interpret"),
                   supports=_lru_supports, priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("block_d", "chunk", "interpret"))
def _lru_kernel(a, b, *, block_d: int = 512, chunk: int = 256,
                interpret: bool = False):
    B, L, D = a.shape
    bd = min(block_d, D)
    ck = min(chunk, L)
    # pad time with identity (a=1, b=0), channels with zeros
    ap, pt = _pad_to(a, (ck,), (1,))
    if pt:
        ap = ap.at[:, L:, :].set(1.0)
    bp, _ = _pad_to(b, (ck,), (1,))
    ap, pd = _pad_to(ap, (bd,), (2,))
    bp, _ = _pad_to(bp, (bd,), (2,))
    out = _lru(ap, bp, block_d=bd, chunk=ck, interpret=interpret)
    return out[:, :L, :D] if (pt or pd) else out


@registry.register("lru_scan", "ref", backends=("ref", "interpret", "pallas"))
@jax.jit
def _lru_ref(a, b):
    return _ref.lru_scan_ref(a, b)


registry.register_blocks("lru_scan", "*", block_d=512, chunk=256)


def lru_scan(a, b, **blocks):
    """h_t = a_t * h_{t-1} + b_t over axis 1 (zero initial state).

    a, b: (B, L, D). ``block_d``/``chunk`` pin kernel tile sizes.
    """
    return registry.dispatch("lru_scan", a, b, **blocks)


# --------------------------------------------------------------------------
# gather_rows / packed_gather_rows — indexed streams (paper C2 / C5c)
# --------------------------------------------------------------------------
def _gather_supports(req: OpRequest) -> bool:
    return (len(req.shapes) >= 2 and len(req.shapes[0]) == 2
            and len(req.shapes[1]) == 1 and "int" in req.dtypes[1])


@registry.register("gather_rows", "pallas", backends=("pallas", "interpret"),
                   supports=_gather_supports, priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("interpret",))
def _gather_kernel(table, idx, *, interpret: bool = False):
    return _gather(table, idx, interpret=interpret)


@registry.register("gather_rows", "ref",
                   backends=("ref", "interpret", "pallas"))
@jax.jit
def _gather_ref(table, idx):
    return _ref.gather_rows_ref(table, idx)


def gather_rows(table, idx):
    """out[i] = table[idx[i]] — the narrow-stream baseline."""
    return registry.dispatch("gather_rows", table, idx)


def _packed_gather_supports(req: OpRequest) -> bool:
    # The TPU compiler refuses the kernel's dynamic one-row reads from the
    # staged window: "Mosaic failed to compile TPU kernel: cannot statically
    # prove that index in dimension 0 is a multiple of 8" (bf16 tables), and
    # the window, which spans the whole table, overflows VMEM at real
    # widths. On the chip the request therefore runs as the XLA gather.
    return req.platform != "tpu" and _gather_supports(req)


@registry.register("packed_gather_rows", "pallas",
                   backends=("pallas", "interpret"),
                   supports=_packed_gather_supports, priority=10, pass_interpret=True)
@partial(jax.jit, static_argnames=("pack", "sort", "interpret"))
def _packed_gather_kernel(table, idx, *, pack: int = 8, sort: bool = True,
                          interpret: bool = False):
    M = idx.shape[0]
    r = (-M) % pack
    order = jnp.argsort(idx) if sort else jnp.arange(M)
    sidx = idx[order]
    if r:
        sidx = jnp.concatenate([sidx, jnp.full((r,), sidx[-1], sidx.dtype)])
    out = _packed_gather(table, sidx, pack=pack, window=table.shape[0],
                         interpret=interpret)[:M]
    inv = jnp.argsort(order) if sort else order
    return out[inv]


registry.register("packed_gather_rows", "ref",
                  backends=("ref", "interpret", "pallas"))(_gather_ref)
registry.register_blocks("packed_gather_rows", "*", pack=8)


def packed_gather_rows(table, idx, *, sort: bool = True, **blocks):
    """Packed/coalesced indexed stream. With ``sort`` (the temporal
    coalescer), gathers are issued in index order and unpermuted after.
    ``pack`` (tuning table, default 8) sets rows per wide flit."""
    return registry.dispatch("packed_gather_rows", table, idx, sort=sort,
                             **blocks)


# --------------------------------------------------------------------------
# instream_scale_reduce — in-stream DMA ops (paper C5b)
# --------------------------------------------------------------------------
def _instream_supports(req: OpRequest) -> bool:
    return (len(req.shapes) >= 1 and len(req.shapes[0]) == 2
            and req.floating())


@registry.register("instream_scale_reduce", "pallas",
                   backends=("pallas", "interpret"),
                   supports=_instream_supports, priority=10,
                   pass_interpret=True)
@partial(jax.jit, static_argnames=("scale", "shift", "block", "interpret"))
def _instream_kernel(x, *, scale: float = 1.0, shift: float = 0.0,
                     block: int = 1024, interpret: bool = False):
    M, D = x.shape
    bm = min(block, M)
    xp, padded = _pad_to(x, (bm,), (0,))
    y, s = _instream(xp, scale=scale, shift=shift, block=bm,
                     interpret=interpret)
    if padded:
        y = y[:M]
        s = s - shift * (xp.shape[0] - M) * D
    return y, s


@registry.register("instream_scale_reduce", "ref",
                   backends=("ref", "interpret", "pallas"))
@partial(jax.jit, static_argnames=("scale", "shift"))
def _instream_ref(x, *, scale: float = 1.0, shift: float = 0.0):
    return _ref.instream_scale_reduce_ref(x, scale=scale, shift=shift)


registry.register_blocks("instream_scale_reduce", "*", block=1024)


def instream_scale_reduce(x, *, scale: float = 1.0, shift: float = 0.0,
                          **blocks):
    """x: (M, D) -> (scale*x + shift, global sum) in one stream pass."""
    return registry.dispatch("instream_scale_reduce", x, scale=scale,
                             shift=shift, **blocks)
