"""The model spine shared by all registered architectures.

Layer layout = unrolled ``prefix`` + ``pattern`` × n_repeats (stacked & scanned
with ``jax.lax.scan``) + unrolled remainder. Scanning the repeated pattern
keeps the HLO size O(pattern) instead of O(n_layers) — essential for
46-layer × 512-device dry-run compiles — and makes activation rematerialization
a per-block policy, mirroring the paper's per-cluster double-buffering.

Heterogeneous periods (gemma2's [local, global]; recurrentgemma's
[rglru, rglru, local]) scan over *pattern periods*: each scan step applies the
whole period, with per-position parameter slices stacked on the leading axis.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels import dispatch as kdispatch
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import recurrent as rec_mod
from repro.models.layers import (Params, apply_mlp, apply_norm, dense_init,
                                 embed_init, mlp_init, norm_init, softcap)

PyTree = Any


# ==========================================================================
# init
# ==========================================================================
def _layer_init(rng, spec: LayerSpec, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 8)
    p: Params = {"pre_norm": norm_init(cfg.d_model, cfg.norm, dtype)}
    if spec.mixer in ("full", "local"):
        p["attn"] = attn_mod.attention_init(ks[0], cfg, dtype)
        if cfg.encoder is not None:  # decoder layer of an enc-dec model
            p["cross_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
            p["cross"] = attn_mod.attention_init(ks[1], cfg, dtype)
    elif spec.mixer == "mla":
        p["attn"] = attn_mod.mla_init(ks[0], cfg, dtype)
    elif spec.mixer == "rglru":
        p["rglru"] = rec_mod.rglru_init(ks[0], cfg, dtype)
    elif spec.mixer == "mamba":
        p["mamba"] = rec_mod.mamba_init(ks[0], cfg, dtype)
    else:
        raise ValueError(spec.mixer)
    if cfg.sandwich_norms:
        p["post_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if spec.mlp == "dense":
        p["mlp_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
        # deepseek-moe's dense prefix layer uses the full d_ff
        p["mlp"] = mlp_init(ks[2], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype)
        if cfg.sandwich_norms:
            p["mlp_post_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    elif spec.mlp == "moe":
        p["mlp_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
        p["moe"] = moe_mod.moe_init(ks[2], cfg, dtype)
        if cfg.sandwich_norms:
            p["mlp_post_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    return p


def _encoder_layer_init(rng, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(rng, 3)
    return {
        "pre_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": attn_mod.attention_init(ks[0], cfg, dtype),
        "mlp_norm": norm_init(cfg.d_model, cfg.norm, dtype),
        "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.gated_mlp, dtype),
    }


def init(rng, cfg: ModelConfig) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    prefix, pattern, n_rep, rem = cfg.layer_specs()
    k_embed, k_pre, k_pat, k_rem, k_head, k_enc, k_pos = jax.random.split(rng, 7)
    params: Params = {
        "embed": {"table": embed_init(k_embed, cfg.vocab_size, cfg.d_model, dtype)},
        "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
    }
    if cfg.learned_pos:
        params["pos_embed"] = {
            "table": embed_init(k_pos, min(cfg.max_position, 1 << 20), cfg.d_model,
                                dtype) * 0.02}
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": dense_init(k_head, cfg.d_model,
                                                  cfg.vocab_size, dtype)}
    if prefix:
        params["prefix"] = [
            _layer_init(k, spec, cfg, dtype)
            for k, spec in zip(jax.random.split(k_pre, len(prefix)), prefix)]
    if n_rep:
        def one_period(k):
            return [_layer_init(kk, spec, cfg, dtype)
                    for kk, spec in zip(jax.random.split(k, len(pattern)), pattern)]
        stacked = [one_period(k) for k in jax.random.split(k_pat, n_rep)]
        params["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)
    if rem:
        params["suffix"] = [
            _layer_init(k, spec, cfg, dtype)
            for k, spec in zip(jax.random.split(k_rem, len(rem)), rem)]
    if cfg.encoder is not None:
        enc_keys = jax.random.split(k_enc, cfg.encoder.n_layers + 2)
        params["encoder"] = {
            "layers": [_encoder_layer_init(k, cfg, dtype)
                       for k in enc_keys[:-2]],
            "final_norm": norm_init(cfg.d_model, cfg.norm, dtype),
            "pos_embed": {"table": embed_init(
                enc_keys[-1], cfg.encoder.n_frames, cfg.d_model, dtype) * 0.02},
        }
    return params


# ==========================================================================
# single-layer application
# ==========================================================================
def _slot_state(state, slot):
    """Slice one slot's recurrent state out of the pooled cache."""
    return jax.tree.map(
        lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, 0), state)


def _merge_slot_state(pool, new, slot):
    """Write a batch-1 recurrent state back into slot ``slot`` of the pool."""
    return jax.tree.map(
        lambda b, s: jax.lax.dynamic_update_slice_in_dim(
            b, s.astype(b.dtype), slot, 0), pool, new)


def _mask_state(new, old, active):
    """Keep ``old`` state rows where ``active`` is False (slots that are not
    in the decode phase must not advance their recurrent carry)."""
    return jax.tree.map(
        lambda n, o: jnp.where(active.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o.astype(n.dtype)), new, old)


def _apply_layer(lp: Params, spec: LayerSpec, cfg: ModelConfig, x, *,
                 positions, enc_out, cache, pos, mode: str, compute_dtype,
                 part=None, active=None, block_tables=None, slot=None,
                 n_valid=None, first_new_pos=0, layer=None):
    """mode: 'full' (train/prefill, builds cache) | 'decode' (single step)
    | 'extend' (chunked prefill: T tokens for ONE slot of the pooled cache)
    | 'verify' (speculative decoding: T tokens for EVERY slot, per-slot
    ``pos``/``n_valid`` arrays, paged full-attention layers only).

    Decode extras: ``active`` ((B,) bool) gates per-slot cache writes;
    ``block_tables`` ((B, P) int32) selects the paged KV layout for full-
    attention layers. Extend extras: ``slot``/``n_valid``/``first_new_pos``
    (traced scalars) — ``first_new_pos`` is where this request's prefill
    started (> 0 when a prefix-cache hit mapped the head of the sequence
    from shared blocks; dense caches mask the rows below it). Paged full-
    attention layers score ``extend`` (the slot's table row) and ``verify``
    through one function, ``attention_paged_extend``. ``layer`` (decode,
    and extend of latent layers; traced scalar): the entry's paged pools
    are stacked over the scanned layers and this layer lives at that
    index. Latent-attention (``mla``) layers refuse ``verify``. Expert
    layers run drop-free in every mode but ``full``. Returns (x,
    new_cache_entry, aux_loss).
    """
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}
    is_local = spec.mixer == "local"
    h = apply_norm(lp["pre_norm"], x, cfg.norm, cfg.norm_eps)
    if spec.mixer == "mla":
        if mode == "full":
            width = (cache["self"]["lat"].shape[-1] if cache is not None
                     else cfg.mla.row_width)
            out, rows = attn_mod.mla_forward(
                lp["attn"], cfg, h, positions=positions,
                compute_dtype=compute_dtype, width=width)
            if cache is not None:
                lat = cache["self"]["lat"]
                new_cache["self"] = {"lat": jnp.zeros_like(lat).at[
                    :, :rows.shape[1]].set(rows.astype(lat.dtype))}
        elif mode == "extend":
            out, new_cache["self"] = attn_mod.mla_extend(
                lp["attn"], cfg, h, cache["self"], pos=pos, n_valid=n_valid,
                slot=slot, compute_dtype=compute_dtype,
                block_tables=block_tables, layer=layer)
        elif mode == "verify":
            raise ValueError(
                "verify_step does not support latent attention (mla): "
                "speculative decoding is not served with latent pools")
        else:
            out, new_cache["self"] = attn_mod.mla_decode(
                lp["attn"], cfg, h, cache["self"], pos=pos,
                compute_dtype=compute_dtype, active=active,
                block_tables=block_tables, layer=layer)
    elif spec.mixer in ("full", "local"):
        bt = block_tables if spec.mixer == "full" else None
        if mode == "full":
            out, (k, v) = attn_mod.attention_forward(
                lp["attn"], cfg, h, is_local=is_local, positions=positions,
                compute_dtype=compute_dtype, part=part)
            if cache is not None:
                new_cache["self"] = _store_kv(cfg, k, v, is_local, cache["self"])
        elif mode in ("extend", "verify") and bt is not None:
            # paged chunked prefill (one slot, through its table row) and
            # verification (every slot) score new tokens alike
            if mode == "extend":
                tables = jax.lax.dynamic_slice(bt, (slot, 0),
                                               (1, bt.shape[1]))
                pos_b, n_b, act = (jnp.reshape(pos, (1,)),
                                   jnp.reshape(n_valid, (1,)), None)
            else:
                tables, pos_b, n_b, act = bt, pos, n_valid, active
            out, new_cache["self"] = attn_mod.attention_paged_extend(
                lp["attn"], cfg, h, cache["self"], pos=pos_b, n_valid=n_b,
                active=act, block_tables=tables, compute_dtype=compute_dtype)
        elif mode == "extend":
            out, new_cache["self"] = attn_mod.attention_extend(
                lp["attn"], cfg, h, cache["self"], is_local=is_local, pos=pos,
                n_valid=n_valid, slot=slot, compute_dtype=compute_dtype,
                first_new_pos=first_new_pos)
        elif mode == "verify":
            raise NotImplementedError(
                "verify_step requires the paged layout on every "
                "attention layer (speculative decoding is gated on "
                "paged all-full-attention configs)")
        else:
            out, new_self = attn_mod.attention_decode(
                lp["attn"], cfg, h, cache["self"], is_local=is_local, pos=pos,
                compute_dtype=compute_dtype, part=part, active=active,
                block_tables=bt, layer=layer)
            new_cache["self"] = new_self
    elif spec.mixer in ("rglru", "mamba"):
        if mode == "verify":
            raise NotImplementedError(
                "verify_step does not support recurrent mixers: speculative "
                "rollback cannot rewind a per-slot carry")
        fwd = rec_mod.rglru_forward if spec.mixer == "rglru" else rec_mod.mamba_forward
        key = spec.mixer
        if mode == "extend":
            st = _slot_state(cache["rec"], slot)
            # first chunk of a (possibly reused) slot starts from zero state
            # — KV rows are position-masked, but recurrent carries are not.
            # The first chunk starts at first_new_pos (0 without a
            # prefix-cache hit; recurrent layers are prefix-incapable, so
            # today this is always pos > 0, kept general for a future
            # carry-restoring cache)
            st = jax.tree.map(
                lambda l: jnp.where(pos > first_new_pos, l,
                                    jnp.zeros_like(l)), st)
            out, new_state = fwd(lp[key], cfg, h, state=st,
                                 compute_dtype=compute_dtype, part=part,
                                 single_step=False, valid_len=n_valid)
            new_cache["rec"] = _merge_slot_state(cache["rec"], new_state, slot)
        else:
            state = None if cache is None else cache["rec"]
            out, new_state = fwd(lp[key], cfg, h, state=state,
                                 compute_dtype=compute_dtype, part=part,
                                 single_step=(mode == "decode"))
            if cache is not None:
                if mode == "decode" and active is not None:
                    new_state = _mask_state(new_state, state, active)
                new_cache["rec"] = new_state
    if cfg.sandwich_norms:
        out = apply_norm(lp["post_norm"], out, cfg.norm, cfg.norm_eps)
    x = x + out

    # cross attention (decoder of enc-dec); enc_out: (B, S_enc, d) or KV cache
    if cfg.encoder is not None and spec.mixer in ("full", "local"):
        if mode in ("extend", "verify"):
            raise NotImplementedError(
                "chunked prefill (extend_step) and speculative verification "
                "(verify_step) do not support enc-dec models — the serve "
                "engine prefills those whole and decodes them one-by-one")
        h = apply_norm(lp["cross_norm"], x, cfg.norm, cfg.norm_eps)
        if mode == "full":
            out, (ck, cv) = attn_mod.attention_forward(
                lp["cross"], cfg, h, is_local=False, positions=None,
                compute_dtype=compute_dtype, causal=False, xkv=enc_out,
                positions_kv=None, part=part)
            if cache is not None:
                new_cache["cross"] = {"k": ck, "v": cv}
        else:
            out, _ = attn_mod.attention_decode(
                lp["cross"], cfg, h, cache["cross"], is_local=False, pos=pos,
                compute_dtype=compute_dtype, part=part, cross=True)
            new_cache["cross"] = cache["cross"]
        x = x + out

    if spec.mlp != "none":
        h = apply_norm(lp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
        if spec.mlp == "dense":
            out = apply_mlp(lp["mlp"], h, cfg.act, cfg.gated_mlp, compute_dtype,
                            part=part)
        else:
            # serving (decode, chunked prefill, verification) drops no
            # token: a token's output must not depend on its batch
            out, aux = moe_mod.moe_forward(lp["moe"], cfg, h,
                                           compute_dtype=compute_dtype,
                                           part=part,
                                           drop_free=mode != "full")
        if cfg.sandwich_norms:
            out = apply_norm(lp["mlp_post_norm"], out, cfg.norm, cfg.norm_eps)
        x = x + out
    if part is not None:
        # sequence-parallel residual stream between blocks: the scan carry
        # saved for backward shards over 'model' (Megatron-SP), collapsing
        # n_layers × (B,S,d) of per-device activation memory.
        x = part.act(x, ("batch", "seq", None))
    return x, new_cache, aux


def _store_kv(cfg: ModelConfig, k, v, is_local: bool, template):
    """Write prefill K/V into a decode cache buffer (template gives S_buf)."""
    S_buf = template["k"].shape[1]
    S = k.shape[1]
    if is_local and cfg.window and S_buf == cfg.window:
        # keep the last `window` positions, rotated so slot = pos % window
        start = max(S - S_buf, 0)
        tail_k, tail_v = k[:, start:], v[:, start:]
        idx = jnp.mod(jnp.arange(start, start + tail_k.shape[1]), S_buf)
        kb = jnp.zeros_like(template["k"]).at[:, idx].set(
            tail_k.astype(template["k"].dtype))
        vb = jnp.zeros_like(template["v"]).at[:, idx].set(
            tail_v.astype(template["v"].dtype))
        return {"k": kb, "v": vb}
    kb = jnp.zeros_like(template["k"]).at[:, :S].set(k.astype(template["k"].dtype))
    vb = jnp.zeros_like(template["v"]).at[:, :S].set(v.astype(template["v"].dtype))
    return {"k": kb, "v": vb}


# ==========================================================================
# stacked application over the layer layout
# ==========================================================================
def _apply_layers(params: Params, cfg: ModelConfig, x, *, positions, enc_out,
                  cache, pos, mode: str, part=None, active=None,
                  block_tables=None, slot=None, n_valid=None,
                  first_new_pos=0):
    compute_dtype = jnp.dtype(cfg.dtype)
    prefix, pattern, n_rep, rem = cfg.layer_specs()
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: dict = {}

    def run(lp, spec, x, centry, layer=None):
        if part is not None:
            # ZeRO-3 style: gather this block's FSDP-sharded weights once,
            # in compute dtype, before use (paper C1: stage the tile, then
            # compute from fast memory).
            lp = part.gather_block(lp, compute_dtype)
        return _apply_layer(lp, spec, cfg, x, positions=positions,
                            enc_out=enc_out, cache=centry, pos=pos, mode=mode,
                            compute_dtype=compute_dtype, part=part,
                            active=active, block_tables=block_tables,
                            slot=slot, n_valid=n_valid,
                            first_new_pos=first_new_pos, layer=layer)

    if prefix:
        new_cache["prefix"] = []
        for i, spec in enumerate(prefix):
            centry = None if cache is None else cache["prefix"][i]
            x, nc, aux = run(params["prefix"][i], spec, x, centry)
            new_cache["prefix"].append(nc)
            aux_total += aux

    if n_rep:
        with_cache = cache is not None
        # paged decode: the full-attention layers' block pools leave the
        # scanned xs/ys and ride in the carry, stacked (n_rep, N, page, K,
        # D); each step scatters its rows at [layer, block, row] and the
        # paged-attention read indexes the layer, so the donated pool is
        # updated in place instead of sliced out and restacked every step
        # (ring buffers, recurrent states and cross caches stay scanned).
        # Latent (mla) pools ride in the carry in chunked prefill too.
        carried = ([j for j, spec in enumerate(pattern)
                    if (mode == "decode" and spec.mixer in ("full", "mla"))
                    or (mode == "extend" and spec.mixer == "mla")]
                   if with_cache and block_tables is not None else [])
        blocks = cache["blocks"] if with_cache else None
        pools = {j: blocks[j]["self"] for j in carried}
        if carried:
            blocks = [{n: e for n, e in c.items()
                       if not (j in pools and n == "self")}
                      for j, c in enumerate(blocks)]

        def period_body(carry, scanned):
            x, aux_acc, pools = carry
            lps, centry, layer = (scanned if with_cache
                                  else (scanned, None, None))
            pools = dict(pools)
            ncs = []
            for j, spec in enumerate(pattern):
                ce = None if centry is None else centry[j]
                if j in pools:
                    ce = dict(ce, self=pools[j])
                    x, nc, aux = run(lps[j], spec, x, ce, layer)
                    pools[j] = nc.pop("self")
                else:
                    x, nc, aux = run(lps[j], spec, x, ce)
                ncs.append(nc)
                aux_acc = aux_acc + aux
            return (x, aux_acc, pools), ncs

        body = period_body
        if cfg.remat == "block":
            body = jax.checkpoint(period_body, prevent_cse=False)
        layers = jnp.arange(n_rep, dtype=jnp.int32) if carried else None
        xs = ((params["blocks"], blocks, layers) if with_cache
              else params["blocks"])
        (x, aux_total, pools), ncs = jax.lax.scan(
            body, (x, aux_total, pools), xs,
            unroll=min(cfg.scan_unroll, n_rep))
        for j, pool in pools.items():
            ncs[j]["self"] = pool
        new_cache["blocks"] = ncs

    if rem:
        new_cache["suffix"] = []
        for i, spec in enumerate(rem):
            centry = None if cache is None else cache["suffix"][i]
            x, nc, aux = run(params["suffix"][i], spec, x, centry)
            new_cache["suffix"].append(nc)
            aux_total += aux

    return x, new_cache, aux_total


def _has_entries(tree) -> bool:
    return len(jax.tree.leaves(tree)) > 0


# ==========================================================================
# encoder (enc-dec models; frontend embeddings are precomputed stubs)
# ==========================================================================
def encode(params: Params, cfg: ModelConfig, frames, *, part=None):
    """frames: (B, S_enc, d_model) precomputed frontend embeddings."""
    compute_dtype = jnp.dtype(cfg.dtype)
    enc = params["encoder"]
    S = frames.shape[1]
    x = frames + enc["pos_embed"]["table"][:S][None].astype(frames.dtype)
    for lp in enc["layers"]:
        h = apply_norm(lp["pre_norm"], x, cfg.norm, cfg.norm_eps)
        out, _ = attn_mod.attention_forward(
            lp["attn"], cfg, h, is_local=False, positions=None,
            compute_dtype=compute_dtype, causal=False, part=part)
        x = x + out
        h = apply_norm(lp["mlp_norm"], x, cfg.norm, cfg.norm_eps)
        x = x + apply_mlp(lp["mlp"], h, cfg.act, cfg.gated_mlp, compute_dtype,
                          part=part)
    return apply_norm(enc["final_norm"], x, cfg.norm, cfg.norm_eps)


# ==========================================================================
# public entry points
# ==========================================================================
def embed_tokens(params, cfg: ModelConfig, tokens, extra_embeds=None):
    from repro.quant import QuantTensor

    table = params["embed"]["table"]
    dt = jnp.dtype(cfg.dtype)
    if isinstance(table, QuantTensor):
        # quantized table (per-row scales, axis=-1): gather the stored rows
        # and their scales FIRST, then dequantize (int4: unpack) only the
        # looked-up rows — never materialize the full (vocab, d) table
        x = table.take_rows(tokens, dtype=dt)
    else:
        if table.dtype != dt:
            # cast BEFORE the (vocab-sharded) gather: the lookup's masked
            # partial-gather + psum then moves compute-dtype bytes, not fp32
            table = table.astype(dt)
        x = table[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if extra_embeds is not None:  # vlm: prepend patch embeddings
        x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
    return x


def logits_fn(params, cfg: ModelConfig, x, part=None):
    """Vocab-sharded logits. Odd vocab sizes (minicpm 122753, whisper 51865)
    are zero-padded to the 'model' axis and masked to -inf — exact for both
    cross-entropy and sampling; padded columns may be returned (callers that
    need exactly V slice, e.g. decode_step)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    from repro.models.layers import grad_dtype_barrier
    x = grad_dtype_barrier(x)  # fp32 loss cotangents re-enter the scan in bf16
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    table = (params["lm_head"]["kernel"] if not cfg.tie_embeddings
             else params["embed"]["table"].T)
    V = cfg.vocab_size
    n_vocab = part.logical_size("vocab") if part is not None else 1
    v_pad = (-(-V // n_vocab) * n_vocab) - V
    table = table.astype(compute_dtype)
    if v_pad:
        table = jnp.pad(table, ((0, 0), (0, v_pad)))
    logits = jnp.einsum("bsd,dv->bsv", x.astype(compute_dtype), table,
                        preferred_element_type=jnp.float32)
    logits = softcap(logits, cfg.final_softcap)
    if part is not None:
        logits = part.act(logits, ("batch", None, "vocab"))
    if v_pad:
        mask = jnp.arange(V + v_pad) < V
        logits = jnp.where(mask[None, None, :], logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits


def _model_kernel_scope(cfg: ModelConfig, part):
    """Registry scope for a whole model graph: cfg.resolved_kernel_backend
    (or an enclosing use_backend scope, which wins) routes every kernelized
    layer — attention, dense/MLP, recurrences, MoE gathers — through the op
    registry. Local path only: under SPMD any kernel scope is *neutralized*
    (not just skipped) so no layer traces a pallas_call inside pjit."""
    if part is not None:
        return kdispatch.spmd_xla_scope()
    be = kdispatch.negotiated_model_backend(cfg.resolved_kernel_backend)
    if be is not None:
        return kdispatch.use_backend(be)
    return contextlib.nullcontext()


def forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None, frames=None,
            cache=None, part=None):
    """Full-sequence forward (training / prefill).

    tokens: (B, S) int32. extra_embeds: (B, S_img, d) for vlm. frames:
    (B, S_enc, d) for enc-dec. cache: decode-cache template to fill (prefill).
    Returns (hidden (B, S_tot, d), new_cache, aux_loss).
    """
    with _model_kernel_scope(cfg, part):
        return _forward(params, cfg, tokens, extra_embeds=extra_embeds,
                        frames=frames, cache=cache, part=part)


def _forward(params, cfg: ModelConfig, tokens, *, extra_embeds=None,
             frames=None, cache=None, part=None):
    x = embed_tokens(params, cfg, tokens, extra_embeds)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)[None, :]
    if cfg.learned_pos and "pos_embed" in params:
        x = x + params["pos_embed"]["table"][:S][None].astype(x.dtype)
    if part is not None:
        x = part.act(x, ("batch", None, None))
    enc_out = None
    if cfg.encoder is not None:
        enc_out = encode(params, cfg, frames, part=part)
    x, new_cache, aux = _apply_layers(params, cfg, x, positions=positions,
                                      enc_out=enc_out, cache=cache, pos=None,
                                      mode="full", part=part)
    return x, new_cache, aux


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *, part=None,
                active=None, block_tables=None):
    """One decode step. tokens: (B, 1) int32; pos: scalar int32 (absolute,
    all sequences aligned) or (B,) int32 (per-slot continuous batching).

    ``active`` ((B,) bool): gate cache writes per slot — slots not in the
    decode phase (free, or mid chunked-prefill) keep their cache/state
    untouched. ``block_tables`` ((B, P) int32): paged KV layout (the cache's
    full-attention leaves are global block pools). Returns
    (logits (B, 1, V), new_cache).
    """
    with _model_kernel_scope(cfg, part):
        return _decode_step(params, cfg, cache, tokens, pos, part=part,
                            active=active, block_tables=block_tables)


def _decode_step(params, cfg: ModelConfig, cache, tokens, pos, *, part=None,
                 active=None, block_tables=None):
    x = embed_tokens(params, cfg, tokens)
    if cfg.learned_pos and "pos_embed" in params:
        tab = params["pos_embed"]["table"]
        if jnp.ndim(pos) > 0:
            x = x + tab[pos][:, None].astype(x.dtype)
        else:
            x = x + jax.lax.dynamic_slice_in_dim(tab, pos, 1, 0)[None].astype(x.dtype)
    x, new_cache, _ = _apply_layers(params, cfg, x, positions=None,
                                    enc_out=None, cache=cache, pos=pos,
                                    mode="decode", part=part, active=active,
                                    block_tables=block_tables)
    logits = logits_fn(params, cfg, x, part)[..., :cfg.vocab_size]
    return logits, new_cache


def extend_step(params, cfg: ModelConfig, cache, tokens, pos, n_valid, slot,
                *, block_tables=None, first_new_pos=0, part=None):
    """Chunked-prefill step: extend ONE slot of the pooled cache by up to T
    tokens. tokens: (1, T) int32 at absolute positions ``pos..pos+T-1``;
    ``n_valid`` (traced scalar) marks the ragged tail — padded positions
    write nothing and never contaminate valid state (attention is causal,
    recurrences take identity steps past ``n_valid``). ``slot`` (traced
    scalar) selects the slot; ``block_tables`` selects the paged layout.
    ``first_new_pos`` (traced scalar) is where this request's prefill
    started: > 0 when a prefix-cache hit mapped positions below it from
    shared pool blocks, so the first chunk begins mid-sequence and the
    paged snapshot below ``first_new_pos`` is readable.

    All of pos/n_valid/slot/first_new_pos trace as scalars, so ONE compiled
    shape serves every chunk of every prompt length, cached prefix or not.
    ``part`` (serve-mode partitioner): the chunk runs under SPMD with the
    pool scatters/gathers partitioned by KV head — the per-layer math is
    identical, so sharded chunked prefill is token-exact with local.
    Returns (logits (1, 1, V) at the last valid position, new_cache).
    """
    with _model_kernel_scope(cfg, part):
        return _extend_step(params, cfg, cache, tokens, pos, n_valid, slot,
                            block_tables=block_tables,
                            first_new_pos=first_new_pos, part=part)


def _extend_step(params, cfg: ModelConfig, cache, tokens, pos, n_valid, slot,
                 *, block_tables=None, first_new_pos=0, part=None):
    x = embed_tokens(params, cfg, tokens)
    T = x.shape[1]
    if cfg.learned_pos and "pos_embed" in params:
        positions = pos + jnp.arange(T, dtype=jnp.int32)
        x = x + params["pos_embed"]["table"][positions][None].astype(x.dtype)
    x, new_cache, _ = _apply_layers(params, cfg, x, positions=None,
                                    enc_out=None, cache=cache, pos=pos,
                                    mode="extend", part=None,
                                    block_tables=block_tables, slot=slot,
                                    n_valid=n_valid,
                                    first_new_pos=first_new_pos)
    h_last = jax.lax.dynamic_slice_in_dim(x, n_valid - 1, 1, 1)
    logits = logits_fn(params, cfg, h_last, part)[..., :cfg.vocab_size]
    return logits, new_cache


def verify_step(params, cfg: ModelConfig, cache, tokens, pos, n_valid, *,
                active=None, block_tables=None, part=None):
    """Speculative-verification step: score T tokens for EVERY slot in one
    pass. tokens: (B, T) int32 — slot b's rows sit at absolute positions
    ``pos[b] .. pos[b]+T-1``; ``n_valid`` ((B,) int32) marks each slot's
    ragged tail (padded rows write nothing); ``active`` ((B,) bool) gates
    whole slots exactly like ``decode_step``. Paged all-full-attention
    configs only (the serve engine gates speculation on the same predicate
    as the prefix cache); each layer scores through
    ``attention_paged_extend``, as a paged prefill chunk does. T is
    static, so one compiled shape serves every scheduler turn at a given
    ``spec_k``.

    Returns (logits (B, T, V) — row t scores position ``pos+t``'s NEXT
    token — and the new cache with all T KV rows written; the engine rolls
    uncommitted rows back by never advancing ``slot_pos`` past the accepted
    prefix, and releasing any speculative pages through the allocator).
    """
    with _model_kernel_scope(cfg, part):
        return _verify_step(params, cfg, cache, tokens, pos, n_valid,
                            active=active, block_tables=block_tables,
                            part=part)


def _verify_step(params, cfg: ModelConfig, cache, tokens, pos, n_valid, *,
                 active=None, block_tables=None, part=None):
    x = embed_tokens(params, cfg, tokens)
    B, T = tokens.shape
    if cfg.learned_pos and "pos_embed" in params:
        positions = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
        x = x + params["pos_embed"]["table"][positions].astype(x.dtype)
    x, new_cache, _ = _apply_layers(params, cfg, x, positions=None,
                                    enc_out=None, cache=cache, pos=pos,
                                    mode="verify", part=None, active=active,
                                    block_tables=block_tables,
                                    n_valid=n_valid)
    logits = logits_fn(params, cfg, x, part)[..., :cfg.vocab_size]
    return logits, new_cache


def lm_loss(params, cfg: ModelConfig, tokens, targets, *, extra_embeds=None,
            frames=None, part=None, loss_chunk: int | None = None):
    """Next-token cross-entropy. targets: (B, S_txt) aligned to the text part.

    With ``loss_chunk``, logits are computed and reduced per sequence chunk
    (never materializing (B, S, V)) — the ogopogo memory optimization.
    """
    hidden, _, aux = forward(params, cfg, tokens, extra_embeds=extra_embeds,
                             frames=frames, part=part)
    if extra_embeds is not None:
        hidden = hidden[:, extra_embeds.shape[1]:]
    lc = cfg.loss_chunk if loss_chunk is None else loss_chunk

    if not lc or lc >= hidden.shape[1]:
        logits = logits_fn(params, cfg, hidden, part)
        loss = _xent(logits, targets)
    else:
        B, S, d = hidden.shape
        n = S // lc
        hs = hidden[:, :n * lc].reshape(B, n, lc, d).transpose(1, 0, 2, 3)
        ts = targets[:, :n * lc].reshape(B, n, lc).transpose(1, 0, 2)

        def body(acc, ht):
            h, t = ht
            lg = logits_fn(params, cfg, h, part)
            return acc + _xent(lg, t) * t.size, None

        # remat the chunk: recompute (B, lc, V) logits in backward instead of
        # letting scan save every chunk's logits (which would defeat chunking)
        body = jax.checkpoint(body, prevent_cse=False)
        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ts))
        loss = tot / (B * n * lc)
        if S > n * lc:  # ragged tail
            lg = logits_fn(params, cfg, hidden[:, n * lc:], part)
            loss = (loss * (B * n * lc) + _xent(lg, targets[:, n * lc:])
                    * (B * (S - n * lc))) / (B * S)
    return loss + 0.01 * aux


def _xent(logits, targets):
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
