"""Explicit collective schedules — Ogopogo's in-router collectives (C5a) and
packed-stream gradient compression (C5c applied to gradient sync).

The paper pushes multicast/broadcast/barrier *into the network* (fork/join in
the routers). On a factored TPU mesh the analogue is staging collectives per
axis so each byte crosses the slow (inter-pod / "D2D") links exactly once at
1/pod_size of the volume:

  hierarchical all-reduce =
      reduce-scatter(intra-pod ICI) → all-reduce(inter-pod) → all-gather(intra)

All primitives are shard_map bodies usable inside jit, differentiable where
needed, and unit-tested on a CPU device mesh.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

PyTree = Any


# --------------------------------------------------------------------------
# hierarchical (in-network style) all-reduce
# --------------------------------------------------------------------------
def hierarchical_allreduce(x: jnp.ndarray, mesh: Mesh, *,
                           intra_axis: str = "data",
                           inter_axis: str = "pod") -> jnp.ndarray:
    """All-reduce over (intra × inter) staged per axis.

    Equivalent to ``psum(x, (intra, inter))`` but the inter-pod stage moves
    1/|intra| of the bytes — the flat crossbar-vs-mesh distinction of the
    paper, measurable in the HLO (benchmarks/fig7).
    """
    n_intra = mesh.shape[intra_axis]

    def body(xl):
        flat = xl.reshape(-1)
        pad = (-flat.shape[0]) % n_intra
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        # stage 1: reduce-scatter inside the pod (fast ICI)
        mine = jax.lax.psum_scatter(flat.reshape(n_intra, -1), intra_axis,
                                    scatter_dimension=0, tiled=False)
        # stage 2: all-reduce my shard across pods (slow D2D, 1/n bytes)
        if inter_axis in mesh.shape:
            mine = jax.lax.psum(mine, inter_axis)
        # stage 3: all-gather inside the pod
        full = jax.lax.all_gather(mine, intra_axis, axis=0, tiled=False)
        out = full.reshape(-1)
        if pad:
            out = out[:-pad]
        return out.reshape(xl.shape)

    spec = P()
    # full-manual shard_map: jax rejects out_specs=P() when axis_names is a
    # strict subset of the mesh axes; manual-ing every axis keeps semantics
    # (inputs here are replicated) and sidesteps the partial-manual limits.
    return jax.shard_map(body, mesh=mesh, in_specs=spec,
                         out_specs=spec, check_vma=False)(x)


def flat_allreduce(x: jnp.ndarray, mesh: Mesh, axes: tuple[str, ...]):
    """Single-stage all-reduce over all axes at once — the Occamy-era
    crossbar baseline for benchmarks/fig7."""
    def body(xl):
        return jax.lax.psum(xl, axes)

    return jax.shard_map(body, mesh=mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)(x)


# --------------------------------------------------------------------------
# multicast / barrier (fork-join analogues)
# --------------------------------------------------------------------------
def multicast(x: jnp.ndarray, mesh: Mesh, axis: str, root: int = 0):
    """Broadcast root's value along ``axis`` (in-router fork)."""
    def body(xl):
        full = jax.lax.all_gather(xl, axis, axis=0, tiled=False)
        return full[root]

    return jax.shard_map(body, mesh=mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)(x)


def barrier(mesh: Mesh, axes: tuple[str, ...]):
    """Join-then-fork barrier: a 1-element psum every rank must reach."""
    def body(t):
        return jax.lax.psum(t, axes)

    tok = jnp.ones((), jnp.int32)
    return jax.shard_map(body, mesh=mesh, in_specs=P(),
                         out_specs=P(), check_vma=False)(tok)


# --------------------------------------------------------------------------
# int8 gradient compression with error feedback — packed irregular streams
# (C5c) applied to gradient sync: 4x fewer bytes over the links. The absmax
# quantizer itself is the quant subsystem's (one implementation for the
# gradient channel, the weight containers, and the KV pools — repro.quant).
# --------------------------------------------------------------------------
from repro.quant import quantize_int8 as _quantize_int8  # noqa: E402


def compressed_psum(x: jnp.ndarray, mesh: Mesh, axes: tuple[str, ...],
                    err: jnp.ndarray | None = None):
    """Mean over ``axes`` with int8 on-the-wire compression + error feedback.

    Returns (mean_estimate fp32, new_error). The residual (x+err − dequant)
    re-enters next step's gradients — standard EF-SGD, here framed as the
    paper's narrow-to-wide stream packing for the gradient channel.
    """
    if err is not None:
        x = x + err
    n = 1
    for a in axes:
        n *= mesh.shape[a]

    def body(xl):
        q, scale = _quantize_int8(xl)
        local_err = xl - q.astype(jnp.float32) * scale
        # int8 crosses the links (the HLO all-gather operand is s8 — 4x fewer
        # bytes than an f32 ring all-reduce), scales are scalars
        qs = jax.lax.all_gather(q, axes, axis=0, tiled=False)      # (n, ...)
        ss = jax.lax.all_gather(scale, axes, axis=0, tiled=False)  # (n,)
        ss = ss.reshape((n,) + (1,) * xl.ndim)
        mean = (qs.astype(jnp.float32) * ss).sum(0) / n
        return mean, local_err

    return jax.shard_map(body, mesh=mesh, in_specs=P(),
                         out_specs=(P(), P()), check_vma=False)(x)
