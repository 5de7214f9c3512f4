"""Shared layer primitives: norms, RoPE, MLPs, initializers.

All modules are functional: ``*_init(rng, ...) -> params`` and a matching
apply function. Params are plain dict pytrees so they stack cleanly under
``jax.lax.scan`` and shard via path-based rules (core/sharding.py).
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch as kdispatch
from repro.quant import QuantTensor

Params = dict[str, Any]


def weight(kernel, compute_dtype):
    """Resolve a parameter leaf for a matmul: quantized containers pass
    through untouched (``dense`` dispatches the weight-quantized GEMM),
    dense arrays cast to the compute dtype as before."""
    if isinstance(kernel, QuantTensor):
        return kernel
    return kernel.astype(compute_dtype)


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------
def dense_init(rng, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32) * scale).astype(dtype)


def embed_init(rng, vocab: int, d: int, dtype) -> jnp.ndarray:
    scale = 1.0 / math.sqrt(d)
    return (jax.random.normal(rng, (vocab, d), dtype=jnp.float32) * scale).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def norm_init(d: int, kind: str, dtype) -> Params:
    p = {"scale": jnp.zeros((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, kind: str, eps: float) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        x = x * jax.lax.rsqrt(var + eps)
        out = x * (1.0 + p["scale"].astype(jnp.float32))
    else:  # layernorm
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        x = (x - mean) * jax.lax.rsqrt(var + eps)
        out = x * (1.0 + p["scale"].astype(jnp.float32)) + p["bias"].astype(jnp.float32)
    return out.astype(dt)


# --------------------------------------------------------------------------
# RoPE
# --------------------------------------------------------------------------
def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention-temperature factor, ``0.1·mscale·ln(factor) + 1``
    (1 where the positions are not stretched)."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, theta: float, scaling=None) -> np.ndarray:
    """Inverse frequencies of a ``dim``-wide rotary embedding, float32.
    Without ``scaling`` (a :class:`~repro.configs.base.RopeScaling`),
    ``theta^(-2i/dim)``; with YaRN, those blended with the same over
    ``factor`` by a linear ramp between the correction dims of
    ``beta_fast`` and ``beta_slow`` rotations at the original context
    (low dims keep the base frequencies, high dims are interpolated)."""
    base = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    if scaling is None:
        return base.astype(np.float32)

    def corr_dim(rot):
        return (dim * math.log(scaling.original_max_position
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(corr_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                  # 1: base frequency, 0: interpolated
    inv = base / scaling.factor * (1 - keep) + base * keep
    return inv.astype(np.float32)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
         scaling=None) -> jnp.ndarray:
    """x: (..., seq, n_heads, head_dim); positions: (..., seq) int32.
    Half-split rotation (``rotate_half``). ``scaling``: YaRN frequencies
    and cos/sin scale (:func:`rope_inv_freq`)."""
    hd = x.shape[-1]
    half = hd // 2
    if scaling is None:
        freq = jnp.exp(-math.log(theta)
                       * jnp.arange(0, half, dtype=jnp.float32) / half)
        mscale = 1.0
    else:
        freq = jnp.asarray(rope_inv_freq(hd, theta, scaling))
        mscale = (yarn_mscale(scaling.factor, scaling.mscale)
                  / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
    ang = positions[..., None].astype(jnp.float32) * freq  # (..., seq, half)
    cos = jnp.cos(ang)[..., None, :]   # (..., seq, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# MLP (gated and plain)
# --------------------------------------------------------------------------
def mlp_init(rng, d: int, d_ff: int, gated: bool, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    p = {"up": {"kernel": dense_init(ks[0], d, d_ff, dtype)},
         "down": {"kernel": dense_init(ks[1], d_ff, d, dtype)}}
    if gated:
        p["gate"] = {"kernel": dense_init(ks[2], d, d_ff, dtype)}
    return p


def _act(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "silu":
        return jax.nn.silu(x)
    if kind == "gelu":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(kind)


def dense(x: jnp.ndarray, w, *, act: str | None = None) -> jnp.ndarray:
    """Linear layer (optionally activation-fused) through the kernel registry.

    A :class:`~repro.quant.QuantTensor` weight dispatches the
    weight-quantized ``ops.gemm_wq`` op (int8/fp8 weights dequantized
    in-tile, fused epilogue) on *every* backend — the ref oracle is the
    dequantize-then-GEMM XLA path, so quantized layers need no call-site
    opt-in. Under an explicit ``use_backend`` kernel scope dense-float
    weights route through ``ops.gemm`` — the Pallas streaming GEMM with its
    fused in-stream epilogue (paper C5b) — with leading dims flattened into
    the row dim. Otherwise it is the plain jnp matmul, bit-identical to the
    historical path.
    """
    if isinstance(w, QuantTensor) and x.ndim >= 2 and w.ndim == 2:
        from repro.kernels import ops
        lead = x.shape[:-1]
        y = ops.gemm_wq(x.reshape(-1, x.shape[-1]), w.q, w.scales, act=act)
        return y.reshape(*lead, w.shape[-1]).astype(x.dtype)
    if isinstance(w, QuantTensor):
        w = w.dequantize(x.dtype)
    if kdispatch.kernel_scope_active() and x.ndim >= 2:
        from repro.kernels import ops
        lead = x.shape[:-1]
        y = ops.gemm(x.reshape(-1, x.shape[-1]), w, act=act)
        return y.reshape(*lead, w.shape[-1]).astype(x.dtype)
    y = x @ w
    return _act(y, act) if act else y


def apply_mlp(p: Params, x: jnp.ndarray, act: str, gated: bool,
              compute_dtype, part=None) -> jnp.ndarray:
    xc = x.astype(compute_dtype)
    if part is None:
        # local path: registry-dispatched dense (kernel backends fuse the
        # activation into the GEMM epilogue; QuantTensor weights dispatch
        # the weight-quantized gemm_wq with in-tile dequant)
        wu = weight(p["up"]["kernel"], compute_dtype)
        if gated:
            h = dense(xc, weight(p["gate"]["kernel"], compute_dtype),
                      act=act) * dense(xc, wu)
        else:
            h = dense(xc, wu, act=act)
        out = dense(h.astype(compute_dtype),
                    weight(p["down"]["kernel"], compute_dtype))
        return out.astype(x.dtype)
    up = xc @ p["up"]["kernel"].astype(compute_dtype)
    up = part.act(up, ("batch",) + (None,) * (up.ndim - 2) + ("mlp",))
    if gated:
        gate = xc @ p["gate"]["kernel"].astype(compute_dtype)
        h = _act(gate, act) * up
    else:
        h = _act(up, act)
    out = h @ p["down"]["kernel"].astype(compute_dtype)
    out = part.act(out, ("batch",) + (None,) * (out.ndim - 1))
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# misc
# --------------------------------------------------------------------------
from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(1,))
def _grad_dtype_barrier(x, dtype_name: str):
    return x


def _gdb_fwd(x, dtype_name):
    return x, None


def _gdb_bwd(dtype_name, _res, ct):
    return (ct.astype(dtype_name),)


_grad_dtype_barrier.defvjp(_gdb_fwd, _gdb_bwd)


def grad_dtype_barrier(x):
    """Identity whose COTANGENT is forced back to x's dtype.

    jnp's no-op casts (x.astype(dt) when x.dtype == dt) record nothing, so an
    f32 cotangent born in the fp32 loss/logits einsum flows *unconverted* into
    the bf16 layer-stack scan, silently doubling every backward activation
    collective and remat buffer (seen as f32[B,S,d] all-reduces in the dry-run
    HLO). This barrier pins the backward boundary to the compute dtype."""
    return _grad_dtype_barrier(x, str(x.dtype))


def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, state: jnp.ndarray | None = None,
                  valid_len=None):
    """Depthwise causal conv. x: (B, L, C); w: (C, K). Returns (y, new_state)
    where state holds the trailing K-1 inputs for streaming decode.

    ``valid_len`` (traced scalar): only the first ``valid_len`` inputs are
    real (chunked prefill with a ragged tail) — new_state then holds the
    K-1 inputs *preceding position valid_len*, not the padded tail."""
    k = w.shape[-1]
    if state is None:
        pad = jnp.zeros(x.shape[:-2] + (k - 1, x.shape[-1]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=-2)                    # (B, L+K-1, C)
    # depthwise conv as sum of shifted slices (K is tiny: 4)
    L = x.shape[-2]
    y = jnp.zeros_like(x, dtype=jnp.float32)
    for i in range(k):
        y = y + xp[..., i:i + L, :].astype(jnp.float32) * w[:, i].astype(jnp.float32)
    if valid_len is None:
        new_state = xp[..., L:, :]                              # last K-1 inputs
    else:
        new_state = jax.lax.dynamic_slice_in_dim(xp, valid_len, k - 1,
                                                 xp.ndim - 2)
    return y.astype(x.dtype), new_state
