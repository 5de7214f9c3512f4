"""Compile the Pallas kernels for a described TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described (``v5e:2x2``) rather than attached. Interpret mode on the CPU
cannot see what it refuses: block shapes the lowering rejects, vector ops
the chip lacks, VMEM overflow. Each test lowers one registered kernel entry
point (the ``pallas`` impl ``registry.dispatch`` runs on a TPU, tile sizes
from the tuning table) at Qwen3-0.6B widths and compiles it.

The topology is described inside a module-scoped fixture, never at import:
the TPU library may be loaded by one process at a time, and every test
worker imports this file.
"""
from __future__ import annotations

import inspect
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops  # noqa: F401  (populates the registry)
from repro.kernels.dispatch import registry

# Qwen3-0.6B: d_model 1024, d_ff 3072, 16/8 heads, head_dim 128, vocab 151936
D_MODEL, D_FF, VOCAB = 1024, 3072, 151936
B, K, G, D = 8, 8, 2, 128                # decode slots, KV heads, group, hd
PAGE, P = 16, 128                        # page rows, pages per slot (2048)
N = B * P + 1                            # pool blocks incl. the null block


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries written for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(sharding, op: str, impl: str, shapes, **kw):
    """Lower and compile the registered ``impl`` of ``op`` for the chip."""
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=sharding)
            for s, d in shapes]
    entry = next(e for e in registry.implementations(op) if e.name == impl)
    blocks = registry.blocks_for(op, registry.request(op, *args))
    names = inspect.signature(entry.fn).parameters
    call = {k: v for k, v in blocks.items() if k in names}
    call.update(kw, interpret=False)
    compiled = jax.jit(lambda *a: entry.fn(*a, **call)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,k,n,act", [
    (B, D_MODEL, D_FF, "silu"),          # MLP up/gate, decode
    (64, D_MODEL, D_FF, None),           # MLP up, one prefill chunk
    (B, D_FF, D_MODEL, None),            # MLP down
    (B, D_MODEL, VOCAB, None),           # lm_head
])
def test_gemm_compiles(one_chip, m, k, n, act):
    _compile(one_chip, "gemm", "pallas",
             [((m, k), "bfloat16"), ((k, n), "bfloat16")], act=act)


def test_flash_attention_compiles(one_chip):
    S = 512
    _compile(one_chip, "flash_attention", "pallas",
             [((K * G, S, D), "bfloat16"), ((K, S, D), "bfloat16"),
              ((K, S, D), "bfloat16")], causal=True)


@pytest.mark.parametrize("kv", ["bfloat16", "int8", "float8_e4m3fn"])
def test_paged_attention_compiles(one_chip, kv):
    shapes = [((B, K, G, D), "bfloat16"), ((N, PAGE, K, D), kv),
              ((N, PAGE, K, D), kv), ((B, P), "int32"), ((B,), "int32")]
    if kv != "bfloat16":
        # quantized pools carry float16 per-row scales
        shapes += [((N, PAGE, K), "float16")] * 2
    _compile(one_chip, "paged_attention", "pallas", shapes)


@pytest.mark.parametrize("pack", [1, 2], ids=["int8", "int4"])
def test_gemm_wq_compiles(one_chip, pack):
    _compile(one_chip, "gemm_wq", "pallas",
             [((B, D_FF), "bfloat16"), ((D_FF // pack, D_MODEL), "int8"),
              ((D_FF // 128, D_MODEL), "float32")])


def test_lru_scan_compiles(one_chip):
    _compile(one_chip, "lru_scan", "pallas",
             [((1, 512, D_MODEL), "float32"), ((1, 512, D_MODEL), "float32")])


def test_gemm_sparse_block_compiles(one_chip):
    _compile(one_chip, "gemm_sparse", "pallas_block",
             [((64, D_MODEL), "bfloat16"), ((D_MODEL, D_FF), "bfloat16"),
              ((D_MODEL // 128, D_FF // 128), "bool")])


def test_gemm_sparse_24_compiles(one_chip):
    _compile(one_chip, "gemm_sparse", "pallas_24",
             [((64, D_MODEL), "bfloat16"), ((D_MODEL // 2, D_FF), "bfloat16"),
              ((D_MODEL // 2, D_FF), "int8")])


def test_gather_rows_compiles(one_chip):
    # the embedding lookup's shape: vocab rows of d_model
    _compile(one_chip, "gather_rows", "pallas",
             [((VOCAB, D_MODEL), "bfloat16"), ((64,), "int32")])
