"""Quickstart: build a model from an assigned architecture config, run a
forward pass, take one training step, and inspect the sharding plan.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, reduced, strategy
from repro.configs.base import ShapeConfig
from repro.models import init, lm_loss
from repro.optim.optimizers import adamw
from repro.train.train_step import make_train_step

# 1) Pick an assigned architecture and shrink it to laptop size (same family:
#    qk-norm GQA transformer — only widths/depth change).
cfg = reduced(get_arch("qwen3-0.6b"))
print(f"arch={cfg.name}  layers={cfg.n_layers}  d_model={cfg.d_model}  "
      f"params≈{cfg.param_count()['total']/1e6:.1f}M")

# 2) Initialize and run a forward pass.
params = init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
tokens = rng.integers(0, cfg.vocab_size, size=(4, 64)).astype(np.int32)
loss = lm_loss(params, cfg, jnp.asarray(tokens), jnp.asarray(tokens))
print(f"initial loss: {float(loss):.4f}  (ln V = {np.log(cfg.vocab_size):.4f})")

# 3) One optimizer step through the production train-step factory.
opt = adamw(1e-3)
step_fn = jax.jit(make_train_step(cfg, opt, strategy("ramora")))
state = {"params": params, "opt": opt.init(params),
         "step": jnp.zeros((), jnp.int32)}
batch = {"tokens": jnp.asarray(tokens), "targets": jnp.asarray(tokens)}
state, metrics = step_fn(state, batch)
print(f"after 1 step: loss={float(metrics['loss']):.4f}  "
      f"grad_norm={float(metrics['grad_norm']):.4f}")

# 4) Show the production sharding plan (what the 16x16 dry-run uses) for a
#    few parameters — logical axes -> mesh axes, no devices needed.
from jax.sharding import AbstractMesh

from repro.core.sharding import Partitioner

full = get_arch("qwen3-0.6b")
shape = ShapeConfig("train_4k", "train", 4096, 256)
mesh = AbstractMesh((16, 16), ("data", "model"))
part = Partitioner(mesh, strategy("ramora"), full, shape)
print("\nproduction sharding plan (16x16 ramora):")
for path, shp in [("embed/table", (151936, 1024)),
                  ("blocks/attn/q_proj/kernel", (14, 1024, 2048)),
                  ("blocks/mlp/up/kernel", (14, 1024, 3072))]:
    spec = part._param_spec(path, len(shp), shp)
    print(f"  {path:34s} {str(shp):18s} -> {spec}")

# 5) Kernel-backend registry: every hot-spot op dispatches through
#    repro.kernels.dispatch — backends are negotiated per call (capability
#    predicates + priorities), with the ref oracle as the universal fallback.
from repro.kernels import ops
from repro.kernels.dispatch import registry, resolve_backend, use_backend

print(f"\nkernel registry (default backend: {resolve_backend().name}):")
for line in registry.describe().splitlines():
    print(f"  {line}")
x = jnp.ones((64, 32))
w = jnp.ones((32, 16))
with use_backend("interpret"):          # Pallas kernels, interpreted on CPU
    y = ops.gemm(x, w, act="gelu")
print(f"registry gemm (interpret backend): out={y.shape}, "
      f"mean={float(y.mean()):.3f}")
# pin kernel tiles per scope (or per StrategyConfig.kernel_blocks):
with use_backend("interpret", blocks={"gemm": {"block_m": 16}}):
    ops.gemm(x, w)
