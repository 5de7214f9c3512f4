"""Mixture-of-Experts: shared + routed experts with top-k gating.

Dispatch is the framework's software analogue of Ogopogo's *packed irregular
streams* (paper §IV-A): each token emits k narrow "requests" (its expert
assignments); we pack them into dense, MXU-aligned per-expert blocks
``[E, C, d]`` before the grouped GEMM, exactly as the paper's DMA extension
packs narrow indexed accesses into wide NoC flits. Tokens are grouped along
the batch axis so the sort/pack stays within a data shard (no cross-device
traffic for routing metadata); the all-to-all happens once, on the packed
blocks, when experts are sharded over the 'model' axis (expert parallelism).

Two dispatch paths:
  * ``dispatch="sort"`` (default): argsort-based packing with capacity drop —
    the paper-faithful packed-stream analogue.
  * ``dispatch="dense"``: one-hot einsum dispatch (GShard-style) — simpler,
    used as the correctness oracle in tests.

Serving (``drop_free=True``: decode, chunked prefill, verification) drops no
token, so a token's output does not depend on which requests share its step:
the packed path runs at a capacity of the group's tokens, so no expert can
overflow. (``jax.lax.ragged_dot`` over the expert-sorted rows computes
fewer rows, but took 2.1x and 2.0x the time at DeepSeek-V2-Lite's decode
and chunk shapes on a TPU v5e.) Training keeps the capacity drop.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.kernels import dispatch as kdispatch
from repro.kernels import ops as kops
from repro.models.layers import Params, apply_mlp, dense_init, mlp_init


def moe_init(rng, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(rng, 6)
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(m.d_expert)
    p: Params = {
        "router": {"kernel": dense_init(ks[0], d, m.n_experts, jnp.float32)},
        "experts": {
            "gate": (jax.random.normal(ks[1], (m.n_experts, d, m.d_expert), jnp.float32)
                     * scale_in).astype(dtype),
            "up": (jax.random.normal(ks[2], (m.n_experts, d, m.d_expert), jnp.float32)
                   * scale_in).astype(dtype),
            "down": (jax.random.normal(ks[3], (m.n_experts, m.d_expert, d), jnp.float32)
                     * scale_out).astype(dtype),
        },
    }
    if m.shared_hidden:
        p["shared"] = mlp_init(ks[4], d, m.shared_hidden, True, dtype)
        if m.shared_gate:
            p["shared_gate"] = {"kernel": dense_init(ks[5], d, 1, dtype)}
    return p


def rows_computed(m: MoEConfig, tokens: int) -> int:
    """Rows the expert matmuls of one drop-free group of ``tokens`` tokens
    run, padding included (what the engine's ``moe_rows_computed`` counts
    per MoE layer): every expert at a capacity of the group's tokens."""
    return m.n_experts * tokens


def capacity(m: MoEConfig, tokens_per_group: int) -> int:
    c = int(math.ceil(tokens_per_group * m.top_k / m.n_experts * m.capacity_factor))
    return max(c, 1)


def _route(p: Params, m: MoEConfig, x_f32: jnp.ndarray):
    """x_f32: (G, T, d) -> (gate_weights (G,T,k), expert_idx (G,T,k), aux_loss)."""
    logits = x_f32 @ p["router"]["kernel"].astype(jnp.float32)   # (G, T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)                    # (G, T, k)
    if m.renorm_topk:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss (mean over groups)
    me = probs.mean(axis=1)                                       # (G, E)
    ce = jnp.zeros_like(me)
    ce = ce.at[jnp.arange(me.shape[0])[:, None, None],
               idx].add(1.0 / (idx.shape[1] * idx.shape[2]))
    aux = (me * ce).sum(-1).mean() * m.n_experts
    return gate, idx, aux


def _dispatch_sort(x, gate, idx, C: int, E: int):
    """Pack tokens into per-expert blocks. x: (T, d); gate/idx: (T, k).

    Returns (xe (E, C, d), combine meta) for one group.
    """
    T, k = idx.shape
    flat_e = idx.reshape(T * k)
    order = jnp.argsort(flat_e)                     # stable
    sorted_e = flat_e[order]
    sorted_tok = order // k
    # position within expert segment = i - first index of that expert value
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    seg_pos = jnp.arange(T * k) - first
    keep = seg_pos < C
    dest = jnp.where(keep, sorted_e * C + seg_pos, E * C)  # overflow row dropped
    xe_flat = jnp.zeros((E * C + 1, x.shape[-1]), x.dtype)
    # the token-row stream is an indexed gather — the paper's packed
    # irregular streams, issued through the *packed* gather (rows coalesced
    # into wide flits in index order, unpermuted after). Registry-dispatched
    # only under an explicit use_backend scope (forward/inference): the
    # Pallas gather defines no JVP, and ambient auto-detection must never
    # reroute a training graph.
    if kdispatch.kernel_scope_active():
        gathered = kops.packed_gather_rows(x, sorted_tok)
    else:
        gathered = x[sorted_tok]
    xe_flat = xe_flat.at[dest].set(gathered)
    xe = xe_flat[: E * C].reshape(E, C, x.shape[-1])
    meta = (dest, sorted_tok, order)
    return xe, meta


def _combine_sort(ye, meta, gate, T: int):
    dest, sorted_tok, order = meta
    E, C, d = ye.shape
    ye_flat = jnp.concatenate([ye.reshape(E * C, d),
                               jnp.zeros((1, d), ye.dtype)], axis=0)
    y_sorted = ye_flat[dest]                         # (T*k, d)
    gate_sorted = gate.reshape(-1)[order].astype(ye.dtype)
    out = jnp.zeros((T, d), ye.dtype)
    out = out.at[sorted_tok].add(y_sorted * gate_sorted[:, None])
    return out


def _expert_ffn_wq(p: Params, xe, compute_dtype):
    """Quantized expert FFN under a kernel scope: each expert's (d, f)
    int8/fp8 weight slab dispatches the ``gemm_wq`` registry op (in-tile
    dequant, fused silu epilogue) — the per-expert grouped GEMM as E
    weight-quantized streaming GEMMs. xe: (G, E, C, d) -> (G, E, C, d)."""
    G, E, C, d = xe.shape
    wg, wu, wd = (p["experts"]["gate"], p["experts"]["up"],
                  p["experts"]["down"])
    outs = []
    for e in range(E):
        x_e = xe[:, e].reshape(G * C, d).astype(compute_dtype)
        h = (kops.gemm_wq(x_e, wg.q[e], wg.scales[e], act="silu")
             * kops.gemm_wq(x_e, wu.q[e], wu.scales[e])).astype(compute_dtype)
        y = kops.gemm_wq(h, wd.q[e], wd.scales[e])
        outs.append(y.reshape(G, C, d))
    return jnp.stack(outs, axis=1).astype(compute_dtype)


def sparsify_experts(p: Params, density: float,
                     *, block: tuple[int, int] = (16, 16)) -> Params:
    """Magnitude block-prune the routed expert FFN weights to ``density``.

    Returns a new params tree whose ``experts/{gate,up,down}`` slabs are
    hard-zeroed outside the kept blocks (so the XLA einsum path and the
    ``gemm_sparse`` kernel path compute the *same* function) plus matching
    per-expert block masks under ``experts/{gate,up,down}_mask`` — the
    operand :func:`_expert_ffn` dispatches through the block-skipping
    kernel under a kernel scope. ``block`` is the (K, N) prune granularity.
    """
    from repro.kernels.gemm_sparse import (apply_block_mask,
                                           block_mask_from_weight)
    ex = dict(p["experts"])
    for name in ("gate", "up", "down"):
        w = ex[name]
        masks = jax.vmap(
            lambda we: block_mask_from_weight(we, block[0], block[1],
                                              density))(w)
        ex[name] = jax.vmap(apply_block_mask)(w, masks).astype(w.dtype)
        ex[name + "_mask"] = masks
    out = dict(p)
    out["experts"] = ex
    return out


def _expert_ffn_sparse(p: Params, xe, compute_dtype):
    """Block-sparse expert FFN under a kernel scope: each expert's pruned
    (d, f) slab dispatches ``gemm_sparse`` with its block mask — masked
    blocks skip the MXU issue entirely (the paper's SpMM utilization arc).
    xe: (G, E, C, d) -> (G, E, C, d)."""
    G, E, C, d = xe.shape
    ex = p["experts"]
    outs = []
    for e in range(E):
        x_e = xe[:, e].reshape(G * C, d).astype(compute_dtype)
        h = (kops.gemm_sparse(x_e, ex["gate"][e].astype(compute_dtype),
                              ex["gate_mask"][e], act="silu")
             * kops.gemm_sparse(x_e, ex["up"][e].astype(compute_dtype),
                                ex["up_mask"][e])).astype(compute_dtype)
        y = kops.gemm_sparse(h, ex["down"][e].astype(compute_dtype),
                             ex["down_mask"][e])
        outs.append(y.reshape(G, C, d))
    return jnp.stack(outs, axis=1).astype(compute_dtype)


def _expert_ffn(p: Params, xe, act: str, compute_dtype, part=None):
    """xe: (G, E, C, d) -> (G, E, C, d) through per-expert gated FFN.

    Sharding: expert-parallel over 'model' when E divides the axis (deepseek-
    moe's 64); otherwise the packed capacity dim is sharded instead (qwen2-
    moe's 60 experts) — C is rounded up to the axis size by the caller.
    Quantized expert weights (QuantTensor — see repro.quant) dequantize via
    ``astype`` on the XLA path; under an explicit kernel scope the local
    path dispatches the weight-quantized grouped GEMM instead. Block-pruned
    experts (:func:`sparsify_experts`) dispatch the block-skipping
    ``gemm_sparse`` under a kernel scope; on the XLA path their hard-zeroed
    slabs make the einsum numerically identical.
    """
    from repro.quant import QuantTensor

    if (part is None and isinstance(p["experts"]["gate"], QuantTensor)
            and kdispatch.kernel_scope_active()):
        return _expert_ffn_wq(p, xe.astype(compute_dtype), compute_dtype)
    if (part is None and "gate_mask" in p["experts"]
            and kdispatch.kernel_scope_active()):
        return _expert_ffn_sparse(p, xe.astype(compute_dtype), compute_dtype)
    w_g = p["experts"]["gate"].astype(compute_dtype)
    w_u = p["experts"]["up"].astype(compute_dtype)
    w_d = p["experts"]["down"].astype(compute_dtype)
    xe = xe.astype(compute_dtype)
    spec = ("batch", "experts", None, None)
    if part is not None:
        if part.logical_size("experts") <= 1:
            spec = ("batch", None, "cap", None)
        xe = part.act(xe, spec)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, w_g)) * jnp.einsum(
        "gecd,edf->gecf", xe, w_u)
    ye = jnp.einsum("gecf,efd->gecd", h, w_d)
    if part is not None:
        ye = part.act(ye, spec)
    return ye


# --------------------------------------------------------------------------
# expert-parallel shard_map dispatch — the paper's "packed irregular streams"
# (C5c) made explicit: tokens' narrow per-slot requests are packed into dense
# per-expert blocks, routed to the expert's shard, and the combine returns as
# an in-network reduction (psum over 'model'), like Ogopogo's in-router joins.
# --------------------------------------------------------------------------
def _slots_for_experts(idx, gate, C: int, E_pad: int):
    """Per group: build (E_pad, C) slot->token and slot->gate maps. idx/gate:
    (T, k). Token index T means 'empty slot'."""
    T, k = idx.shape
    flat_e = idx.reshape(T * k)
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    sorted_tok = (order // k).astype(jnp.int32)
    sorted_gate = gate.reshape(T * k)[order]
    first = jnp.searchsorted(sorted_e, sorted_e, side="left")
    seg_pos = jnp.arange(T * k) - first
    # slot (e, c) <- sorted position p where sorted_e[p] == e and seg_pos == c
    dest = jnp.where(seg_pos < C, sorted_e * C + seg_pos, E_pad * C)
    slot_tok = jnp.zeros((E_pad * C + 1,), jnp.int32).at[dest].set(sorted_tok)
    filled = jnp.zeros((E_pad * C + 1,), jnp.bool_).at[dest].set(True)
    slot_tok = jnp.where(filled, slot_tok, T)[:E_pad * C]
    slot_gate = jnp.zeros((E_pad * C + 1,), jnp.float32).at[dest].set(
        sorted_gate.astype(jnp.float32))[:E_pad * C]
    return slot_tok.reshape(E_pad, C), slot_gate.reshape(E_pad, C)


def moe_forward_ep(p: Params, cfg: ModelConfig, x, *, compute_dtype, part,
                   drop_free: bool = False):
    """shard_map expert-parallel MoE: experts (padded up to the 'model' axis
    size) live on their shard; packed per-expert blocks are gathered locally
    and partial outputs joined with one psum (Ogopogo's in-router join).
    ``drop_free``: capacity is the group's tokens."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    mesh = part.mesh
    n_model = mesh.shape["model"]
    batch_axes = part.axis_map["batch"]
    # 2D-EP (fsdp2d): batch is ALSO sharded over 'model'. Each expert shard
    # all-gathers its data-row's token groups over 'model', runs its local
    # experts on all of them, and reduce-scatters the combined outputs back —
    # the paper's packed-stream dispatch staged over both mesh axes.
    two_d = "model" in (batch_axes or ())
    B, S, d = x.shape
    G = B if S > 1 else 1
    T = (B * S) // G
    E, k = m.n_experts, m.top_k
    E_pad = -(-E // n_model) * n_model
    e_loc = E_pad // n_model
    n_batch_shards = part.logical_size("batch")
    if S > 1:
        C = T if drop_free else capacity(m, T)
        bspec = P(batch_axes, None, None)
    else:
        # decode: one group; tokens shard over the batch axes; drop-free C
        C = max(1, T // max(n_batch_shards, 1))
        bspec = P(None, batch_axes, None)
    xc = x.reshape(G, T, d)

    # pad expert weights to E_pad on the compute-dtype copies
    def padw(w):
        w = w.astype(compute_dtype)
        if E_pad > E:
            w = jnp.concatenate(
                [w, jnp.zeros((E_pad - E,) + w.shape[1:], w.dtype)], axis=0)
        return w

    wg, wu, wd = (padw(p["experts"]["gate"]), padw(p["experts"]["up"]),
                  padw(p["experts"]["down"]))
    router = p["router"]["kernel"].astype(jnp.float32)
    wspec = P("model", None, None)

    def body(xl, rl, wgl, wul, wdl):
        if two_d and S > 1:
            # gather this data-row's groups from every model shard
            xl = jax.lax.all_gather(xl, "model", axis=0, tiled=True)
        gl, tl, _ = xl.shape
        logits = xl.astype(jnp.float32) @ rl                   # (gl, tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, k)
        if m.renorm_topk:
            gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=1)
        ce = jnp.zeros_like(me).at[
            jnp.arange(gl)[:, None, None], idx].add(1.0 / (tl * k))
        aux_g = (me * ce).sum(-1) * E                          # (gl,)

        slot_tok, slot_gate = jax.vmap(
            lambda ii, gg: _slots_for_experts(ii, gg, C, E_pad))(idx, gate)
        e0 = jax.lax.axis_index("model") * e_loc
        my_tok = jax.lax.dynamic_slice_in_dim(slot_tok, e0, e_loc, axis=1)
        my_gate = jax.lax.dynamic_slice_in_dim(slot_gate, e0, e_loc, axis=1)

        # pack: gather tokens into my experts' dense blocks (empty slot -> 0)
        xpad = jnp.concatenate(
            [xl, jnp.zeros((gl, 1, d), xl.dtype)], axis=1)     # row tl = zeros
        xe = jax.vmap(lambda xg, tk: xg[tk])(xpad, my_tok)     # (gl, e_loc, C, d)
        xe = xe.astype(compute_dtype)
        h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, wgl)) * jnp.einsum(
            "gecd,edf->gecf", xe, wul)
        ye = jnp.einsum("gecf,efd->gecd", h, wdl)              # (gl, e_loc, C, d)
        ye = ye * my_gate[..., None].astype(ye.dtype)

        # combine: scatter-add my experts' slots back, join across shards
        def comb(yg, tk):
            return jnp.zeros((tl + 1, d), ye.dtype).at[
                tk.reshape(-1)].add(yg.reshape(-1, d))[:tl]
        y = jax.vmap(comb)(ye, my_tok)                         # (gl, tl, d)
        if two_d and S > 1:
            # in-network join + return each group to its model shard
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=0,
                                     tiled=True)
            j = jax.lax.axis_index("model")
            g_per = gl // jax.lax.psum(1, "model")
            aux_g = jax.lax.dynamic_slice_in_dim(aux_g, j * g_per, g_per, 0)
        else:
            y = jax.lax.psum(y, "model")
        return y, aux_g

    y, aux_g = jax.shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(None, None), wspec, wspec, wspec),
        out_specs=(bspec, P(bspec[0] if G > 1 else None)),
        check_vma=False)(xc, router, wg, wu, wd)
    return y.reshape(B, S, d).astype(x.dtype), aux_g.mean()


def moe_forward(p: Params, cfg: ModelConfig, x, *, compute_dtype, part=None,
                dispatch: str = "sort", drop_free: bool = False):
    """x: (B, S, d). Returns (y, aux_loss). ``drop_free`` (serving): no
    token is dropped and each token's output is independent of the others
    in its step (module docstring)."""
    m = cfg.moe
    B, S, d = x.shape
    if (part is not None and dispatch == "sort"
            and part.axis_size("model") > 1 and part.strategy.expert_parallel):
        y, aux = moe_forward_ep(p, cfg, x, compute_dtype=compute_dtype,
                                part=part, drop_free=drop_free)
        return _add_shared(p, cfg, x, y, compute_dtype, part), aux
    G = B if S > 1 else 1                   # group along batch; decode: one group
    T = (B * S) // G
    xg = x.reshape(G, T, d)
    gate, idx, aux = _route(p, m, xg.astype(jnp.float32))
    C = T if drop_free else capacity(m, T)
    E = m.n_experts
    if part is not None and part.logical_size("experts") <= 1:
        mult = part.logical_size("cap")
        if mult > 1:  # round capacity up so the packed dim shards evenly
            C = -(-C // mult) * mult

    if dispatch == "dense":
        onehot = jax.nn.one_hot(idx, E, dtype=compute_dtype)      # (G, T, k, E)
        comb = (onehot * gate[..., None].astype(compute_dtype)).sum(2)  # (G, T, E)
        xe = jnp.einsum("gtd,gte->getd", xg.astype(compute_dtype), onehot.sum(2))
        ye = _expert_ffn(p, xe, cfg.act, compute_dtype, part)
        y = jnp.einsum("getd,gte->gtd", ye, comb)
    else:
        xe, meta = jax.vmap(lambda xx, gg, ii: _dispatch_sort(xx, gg, ii, C, E))(
            xg, gate, idx)
        ye = _expert_ffn(p, xe, cfg.act, compute_dtype, part)
        y = jax.vmap(lambda yy, mm_a, mm_b, mm_c, gg: _combine_sort(
            yy, (mm_a, mm_b, mm_c), gg, T))(ye, *meta, gate)

    y = y.reshape(B, S, d).astype(x.dtype)
    return _add_shared(p, cfg, x, y, compute_dtype, part), aux


def _add_shared(p: Params, cfg: ModelConfig, x, y, compute_dtype, part=None):
    m = cfg.moe
    if not m.shared_hidden:
        return y
    ys = apply_mlp(p["shared"], x, cfg.act, True, compute_dtype, part=part)
    if m.shared_gate:
        g = jax.nn.sigmoid((x.astype(compute_dtype)
                            @ p["shared_gate"]["kernel"].astype(compute_dtype)))
        ys = (ys.astype(compute_dtype) * g).astype(x.dtype)
    return y + ys
