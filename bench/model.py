"""Configurations and weights.

A configuration file holds the published ``config.json`` keys of the model
(``hidden_size``, ``num_hidden_layers``, ...) beside the benchmark's own
keys: ``source``, ``reduced``, ``assumed``, ``deployment``, ``program_arch``
(the program's registry entry whose layer kinds match) and ``engine`` (the
serving options a deployment sets). :func:`program_config` maps the
published keys onto the program's ``ModelConfig``; nothing else of the
registry entry is trusted for a size.

Weights are the benchmark's: :func:`make_weights` draws them from the seed
on the device in one jitted call, in bfloat16, in a plain layout of its own
(``wq`` is ``(layers, d_model, heads * head_dim)``, a norm is its weight, as
published). The reference reads that layout; :func:`to_program` hands the
same arrays to the program in its layout, so no second copy is made.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

from bench.costs import Shape

def load_config(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def program_config(c: dict):
    """The program's ``ModelConfig`` for configuration ``c``: the registry
    entry named by ``program_arch`` with every size from the published keys,
    bf16 weights, the paged engine and the kernels chosen by platform."""
    from repro.configs import get_arch

    s = Shape.from_config(c)
    eng = c["engine"]
    base = get_arch(c["program_arch"])
    return dataclasses.replace(
        base, n_layers=s.n_layers, d_model=s.d_model, n_heads=s.n_heads,
        n_kv_heads=s.n_kv_heads, head_dim=s.head_dim, d_ff=s.d_ff,
        vocab_size=s.vocab, tie_embeddings=s.tied,
        qk_norm=c["model_type"] == "qwen3",
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype="bfloat16", param_dtype=c["torch_dtype"],
        paged_kv=True, page_size=eng["page_size"],
        prefill_chunk=eng["prefill_chunk"],
        prefix_cache=bool(eng["prefix_cache"]),
        kernel_backend=eng["kernel_backend"])


def weight_shapes(c: dict) -> dict[str, tuple[int, ...]]:
    s = Shape.from_config(c)
    L, d, hd, f = s.n_layers, s.d_model, s.head_dim, s.d_ff
    shapes = {
        "embed": (s.vocab, d),
        "final_norm": (d,),
        "attn_norm": (L, d), "mlp_norm": (L, d),
        "wq": (L, d, s.n_heads * hd), "wk": (L, d, s.n_kv_heads * hd),
        "wv": (L, d, s.n_kv_heads * hd), "wo": (L, s.n_heads * hd, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }
    if c["model_type"] == "qwen3":
        shapes["q_norm"] = (L, hd)
        shapes["k_norm"] = (L, hd)
    if not s.tied:
        shapes["lm_head"] = (d, s.vocab)
    return shapes


def make_weights(c: dict, seed: int, device=None):
    """All weights from ``seed`` in one jitted call on ``device``, bf16.
    Matrices are normal with variance 1/fan_in (embedding rows: 1/d_model);
    norm weights are ones, as the published initialisation has them."""
    import jax
    import jax.numpy as jnp

    shapes = weight_shapes(c)
    names = sorted(shapes)

    def build(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for k, name in zip(keys, names):
            shp = shapes[name]
            if name.endswith("norm"):
                out[name] = jnp.ones(shp, jnp.bfloat16)
                continue
            fan_in = shp[-1] if name == "embed" else shp[-2]
            out[name] = (jax.random.normal(k, shp, jnp.bfloat16)
                         * jnp.bfloat16(1.0 / math.sqrt(fan_in)))
        return out

    with jax.default_device(device or jax.devices()[0]):
        return jax.jit(build)(jax.random.key(seed_key(seed)))


def seed_key(seed: int) -> int:
    """Fold a seed of any size into the 32 bits a PRNG key takes, so that
    every distinct seed of up to 64 bits gives a distinct key stream."""
    seed = int(seed)
    return (seed ^ (seed >> 32)) & 0xFFFFFFFF


def to_program(w: dict, c: dict):
    """The program's parameter tree over the same arrays: its norms store
    ``weight - 1`` (exact for the ones drawn here), its layers are one
    scanned period."""
    def norm(x):
        return {"scale": (x - 1).astype(x.dtype)}

    attn = {"q_proj": {"kernel": w["wq"]}, "k_proj": {"kernel": w["wk"]},
            "v_proj": {"kernel": w["wv"]}, "o_proj": {"kernel": w["wo"]}}
    if "q_norm" in w:
        attn["q_norm"] = norm(w["q_norm"])
        attn["k_norm"] = norm(w["k_norm"])
    block = {"pre_norm": norm(w["attn_norm"]), "attn": attn,
             "mlp_norm": norm(w["mlp_norm"]),
             "mlp": {"gate": {"kernel": w["w_gate"]},
                     "up": {"kernel": w["w_up"]},
                     "down": {"kernel": w["w_down"]}}}
    p = {"embed": {"table": w["embed"]}, "final_norm": norm(w["final_norm"]),
         "blocks": [block]}
    if "lm_head" in w:
        p["lm_head"] = {"kernel": w["lm_head"]}
    return p


def check_program_layout(params, cfg) -> None:
    """Raise unless ``params`` has exactly the program's own parameter
    structure and shapes for ``cfg``."""
    import jax

    from repro.models import init

    want = jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))
    got_s, want_s = (jax.tree.structure(params), jax.tree.structure(want))
    if got_s != want_s:
        raise ValueError(f"parameter tree differs from the program's:\n"
                         f"{got_s}\n!=\n{want_s}")
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        if a.shape != b.shape:
            raise ValueError(f"parameter shape {a.shape} != {b.shape}")
