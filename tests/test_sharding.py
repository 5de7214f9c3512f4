"""Partitioner rules: strategy policies, divisibility fallbacks, cache specs.
Uses AbstractMesh — no devices required."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch, get_shape, strategy
from repro.core.sharding import Partitioner

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _part(arch="deepseek-7b", strat="ramora", shape="train_4k", mesh=MESH,
          mode="train"):
    return Partitioner(mesh, strategy(strat, multi_pod=("pod" in mesh.shape)),
                       get_arch(arch), get_shape(shape), mode=mode)


# --------------------------------------------------------------------------
# strategy policies (the paper's three generations)
# --------------------------------------------------------------------------
def test_occamy_replicates_params():
    p = _part(strat="occamy")
    spec = p._param_spec("blocks/attn/q_proj/kernel", 3, (15, 4096, 4096))
    assert spec == P(None, None, None)
    # batch over every chip (pure DP)
    assert p.axis_map["batch"] == ("data", "model")


def test_ramora_tp_fsdp():
    p = _part(strat="ramora")
    assert p._param_spec("blocks/attn/q_proj/kernel", 3,
                         (15, 4096, 4096)) == P(None, "data", "model")
    assert p._param_spec("blocks/mlp/down/kernel", 3,
                         (15, 11008, 4096)) == P(None, "model", "data")
    assert p._param_spec("embed/table", 2, (102400, 4096)) == P("model", "data")
    assert p.axis_map["batch"] == ("data",)


def test_ogopogo_pod_axis():
    p = _part(strat="ogopogo", mesh=MESH3)
    assert p.axis_map["batch"] == ("pod", "data")
    # params FSDP over data only (replicated over pod; grads all-reduce there)
    assert p._param_spec("blocks/mlp/up/kernel", 3,
                         (15, 4096, 11008)) == P(None, "data", "model")


# --------------------------------------------------------------------------
# divisibility fallbacks
# --------------------------------------------------------------------------
def test_qwen3_kv_heads_replicate():
    """qwen3: 8 kv heads on a 16-way model axis -> replicate that dim."""
    p = _part("qwen3-0.6b")
    spec = p.spec(("batch", None, "heads", None), (256, 4096, 8, 128))
    assert spec == P("data", None, None, None)
    # q heads (16) do shard
    spec_q = p.spec(("batch", None, "heads", None), (256, 4096, 16, 128))
    assert spec_q == P("data", None, "model", None)


def test_moe_expert_parallel_divisibility():
    # deepseek-moe: 64 % 16 == 0 -> experts sharded over model
    p = _part("deepseek-moe-16b")
    assert p.axis_map["experts"] == ("model",)
    spec = p._param_spec("blocks/moe/experts/up", 4, (13, 64, 2048, 1408))
    assert spec == P(None, "model", "data", None)
    # qwen2-moe: 60 % 16 != 0 -> replicate experts, TP-shard expert d_ff
    p2 = _part("qwen2-moe-a2.7b")
    assert p2.axis_map["experts"] is None
    spec2 = p2._param_spec("blocks/moe/experts/up", 4, (11, 60, 2048, 1408))
    assert spec2 == P(None, None, "data", "model")


def test_odd_vocab_replicates_embed_dim():
    """minicpm vocab 122753 is prime-ish: not divisible by 16 -> replicated."""
    p = _part("minicpm-2b")
    spec = p._param_spec("embed/table", 2, (122753, 2304))
    assert spec[0] is None


# --------------------------------------------------------------------------
# batches, caches, scalars
# --------------------------------------------------------------------------
def test_batch_sharding_leading_axis():
    p = _part()
    sh = p.batch_sharding({"tokens": jnp.zeros((256, 4096), jnp.int32)})
    assert sh["tokens"].spec == P("data", None)


def test_decode_cache_context_parallel():
    """long_500k (batch 1 < data axis): KV length sharded over 'data'."""
    p = _part("gemma2-27b", shape="long_500k", mode="decode")
    assert "data" in (p.axis_map["kv"] or ())
    # abstract shapes only — a materialized 500k-token cache is ~49 GB
    sh = p.cache_sharding({"blocks": {"self": {
        "k": jax.ShapeDtypeStruct((23, 1, 524288, 16, 128), jnp.bfloat16)}}})
    assert sh["blocks"]["self"]["k"].spec[2] == "data"


def test_decode_cache_batch_sharded():
    """decode_32k (batch 128 >= data axis): batch over 'data', length whole."""
    p = _part("gemma2-27b", shape="decode_32k", mode="decode")
    sh = p.cache_sharding({"blocks": {"self": {
        "k": jax.ShapeDtypeStruct((23, 128, 32768, 16, 128), jnp.bfloat16)}}})
    spec = sh["blocks"]["self"]["k"].spec
    assert spec[1] == "data"


def test_gather_block_drops_fsdp():
    """ZeRO-3 gather: FSDP axis dropped, TP kept, dtype cast applied."""
    p = _part()
    layer = {"attn": {"q_proj": {"kernel": jnp.zeros((4096, 4096))}}}
    # abstract mesh cannot run with_sharding_constraint eagerly -> trace it
    def f(lp):
        return p.gather_block(lp, jnp.bfloat16)
    out = jax.eval_shape(f, layer)
    k = out["attn"]["q_proj"]["kernel"]
    assert k.dtype == jnp.bfloat16


def test_scalar_sharding_replicated():
    assert _part().scalar_sharding().spec == P()
