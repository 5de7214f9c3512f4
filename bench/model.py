"""Weights, whatever the architecture.

A configuration file holds the published ``config.json`` keys of the model
(``hidden_size``, ``num_hidden_layers``, ``model_type``, ...) beside the
benchmark's own keys: ``source``, ``reduced``, ``assumed``, ``deployment``,
``program_arch`` (the program's registry entry whose layer kinds match) and
``engine`` (the serving options a deployment sets). ``model_type`` names the
architecture's module (``bench/arch/``), which maps the published keys onto
the program's ``ModelConfig`` and lays out the weights.

Weights are the benchmark's: :func:`make_weights` draws them from the seed
on the device in one jitted call, in bfloat16, in the architecture's plain
layout. The reference reads that layout; the module's ``to_program`` hands
the same arrays to the program in its layout, so no second copy is made.
"""
from __future__ import annotations

import math


def make_weights(shapes: dict[str, tuple[int, ...]], seed: int,
                 device=None):
    """All weights of ``shapes`` (an architecture's ``weight_shapes``) from
    ``seed`` in one jitted call on ``device``, bf16. Matrices are normal
    with variance 1/fan_in (``embed``: 1/d_model, its last axis; every
    other: its axis -2); weights named ``*norm`` are ones, as the published
    initialisation has them."""
    import jax
    import jax.numpy as jnp

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
