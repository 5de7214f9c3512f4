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
L = 4                                    # layers of a stacked pool


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
    # pools stacked over L layers, read at a traced layer index
    shapes = [((B, K, G, D), "bfloat16"), ((L, N, PAGE, K, D), kv),
              ((L, N, PAGE, K, D), kv), ((B, P), "int32"), ((B,), "int32"),
              ((), "int32")]
    if kv != "bfloat16":
        # quantized pools carry float16 per-row scales
        shapes += [((L, N, PAGE, K), "float16")] * 2
    _compile(one_chip, "paged_attention", "pallas", shapes)


def test_paged_mla_compiles(one_chip):
    """Absorbed latent-attention decode at DeepSeek-V2-Lite's widths: 16
    heads over 640-column latent rows (512 latent + 64 RoPE key, padded to
    whole lane tiles), 512 value columns, a stacked pool read at a traced
    layer, 1,024 pages per slot (16k tokens)."""
    H_MLA, W_MLA, P_MLA = 16, 640, 1024
    _compile(one_chip, "paged_mla", "pallas",
             [((B, H_MLA, W_MLA), "bfloat16"),
              ((L, B * P_MLA + 1, PAGE, W_MLA), "bfloat16"),
              ((B, P_MLA), "int32"), ((B,), "int32"), ((), "int32")],
             dv=512)


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


def test_decode_updates_pool_in_place(one_chip, monkeypatch):
    """The engine's decode program, compiled for the chip with the cache
    donated, writes the stacked paged pool in place: its temporaries do not
    grow with the pool, and no op copies, restacks or allocates a buffer of
    the stacked pool's shape. A scan that slices each layer's pool out and
    restacks it holds a pool-sized temporary and fails both checks."""
    import re

    from repro.configs import get_arch, reduced
    from repro.models import init
    from repro.models.cache import init_cache
    from repro.serve import ServeEngine

    # the registry picks the Pallas kernels by the platform JAX runs on;
    # the program is built for the described chip, so name its platform
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = reduced(get_arch("qwen3-0.6b")).replace(
        n_layers=3, d_model=256, n_heads=4, n_kv_heads=2, head_dim=D,
        d_ff=512, vocab_size=1024, dtype="bfloat16", param_dtype="bfloat16")
    slots, max_len = 3, 256
    eng = ServeEngine(cfg, init(jax.random.PRNGKey(0), cfg),
                      max_slots=slots, max_len=max_len, paged=True,
                      page_size=PAGE, max_blocks=8, kernel_backend="auto")

    def spec(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    i32 = jnp.int32
    step = [((slots, 1), i32), ((slots,), i32), ((slots,), jnp.bool_),
            ((slots, eng.n_pages), i32), ((slots,), jnp.float32),
            ((slots,), i32), ((slots,), jnp.float32),
            ((slots, 2), jnp.uint32), ((slots,), jnp.uint32)]
    params = jax.tree.map(spec, eng.params)
    temps, pools = [], []
    # pools well past what the compiler may stage in on-chip memory
    for n_blocks in (1025, 2049):
        cache = jax.tree.map(spec, jax.eval_shape(
            lambda: init_cache(cfg, slots, max_len, n_blocks=n_blocks,
                               page_size=PAGE)))
        args = [params, cache] + [jax.ShapeDtypeStruct(
            s, d, sharding=one_chip) for s, d in step]
        with eng._kernel_scope():
            compiled = jax.jit(eng._decode_all, donate_argnums=(1,)).lower(
                *args).compile()
        temps.append(compiled.memory_analysis().temp_size_in_bytes)
        pools.append(sum(a.size * a.dtype.itemsize
                         for a in jax.tree.leaves(cache["blocks"])))
        stacked = "bf16[%d,%d,%d,%d,%d]" % (cfg.n_layers, n_blocks, PAGE,
                                            cfg.n_kv_heads, D)
        hlo = compiled.as_text()
        assert "tpu_custom_call" in hlo          # the Pallas kernels ran
        bad = [line.strip()[:120] for line in hlo.splitlines()
               if re.search(r"= " + re.escape(stacked) + r"\S* "
                            r"(dynamic-update-slice|copy|custom-call\(\))",
                            line)
               or (stacked in line and "AllocateBuffer" in line)]
        assert not bad, bad
    assert temps[1] - temps[0] < 0.1 * (pools[1] - pools[0]), (temps, pools)
