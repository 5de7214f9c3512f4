"""Logical-axis sharding rules → NamedShardings (the "NoC routing table").

The ``Partitioner`` maps logical tensor axes (batch/seq/heads/mlp/vocab/
experts/kv) and parameter paths to mesh axes according to the active strategy
(occamy = flat crossbar-era DP; ramora = factored 2D mesh TP+FSDP;
ogopogo = + pod axis, sequence sharding, hierarchical collectives).

Divisibility is checked per dim: when a dim does not divide by the assigned
mesh axes, the axis is dropped (replicated) rather than padded — e.g. qwen3's
8 KV heads on a 16-way model axis, or qwen2-moe's 60 experts.
"""
from __future__ import annotations

import logging
import math
import re
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, AxisType, Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, StrategyConfig

PyTree = Any

log = logging.getLogger("repro.sharding")


def _auto_axes(mesh):
    """``mesh`` with every axis in Auto mode. The rules below are hints to
    the partitioner. ``jax.make_mesh`` makes Explicit axes by default, and
    under those a sharding is part of each array's type: the FSDP embed
    gather then asks for one axis on two dims (``DuplicateSpecError``) and
    every contraction over a sharded dim must name its output sharding."""
    if AxisType.Explicit not in mesh.axis_types:
        return mesh
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if isinstance(mesh, Mesh):
        return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)
    return AbstractMesh(mesh.axis_sizes, mesh.axis_names, axis_types=auto)


def _axes_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    return math.prod(mesh.shape[a] for a in axes)


class Partitioner:
    def __init__(self, mesh: Mesh, strategy: StrategyConfig, cfg: ModelConfig,
                 shape: ShapeConfig | None = None, mode: str = "train"):
        self.mesh = mesh = _auto_axes(mesh)
        self.strategy = strategy
        self.cfg = cfg
        self.shape = shape
        self.mode = mode
        # divisibility drops recorded per (label, dim): surfaced by
        # launch/serve.py so serve-mode misconfigs (e.g. 8 KV heads on a
        # 16-way model axis) are visible instead of silently replicating.
        self.dropped: list[dict] = []
        self._drop_seen: set = set()
        st = strategy
        have_pod = "pod" in mesh.shape
        if mode == "serve":
            # Serving data plane: params and activations replicated, the
            # paged KV block pools (and per-row quant scales) sharded by KV
            # head over 'model'. Block tables / lengths / per-slot scalars
            # stay replicated scalar-prefetch operands.
            pool = ("model",) if "model" in mesh.shape else None
            self.axis_map = {"batch": None, "seq": None, "heads": None,
                             "kv": None, "mlp": None, "vocab": None,
                             "experts": None, "fsdp": None, "tp": None,
                             "expert": None, "embed_fsdp": None,
                             "kv_pool": pool}
        elif st.name == "occamy":
            # flat crossbar-era: every chip is a DP rank, params replicated
            flat = tuple(a for a in (("pod",) if have_pod else ())
                         + ("data", "model"))
            self.axis_map = {"batch": flat, "seq": None, "heads": None,
                             "kv": None, "mlp": None, "vocab": None,
                             "experts": None, "fsdp": None, "tp": None,
                             "expert": None, "embed_fsdp": None}
        else:
            batch = (("pod", "data") if have_pod else ("data",))
            train_like = mode in ("train", "prefill")
            seq_shard = ("model",) if (st.seq_shard and train_like) else None
            fsdp = ("data",) if (st.fsdp and train_like) else None
            tp = ("model",) if st.tensor_parallel else None
            ep = None
            if (st.expert_parallel and cfg.moe is not None
                    and cfg.moe.n_experts % mesh.shape["model"] == 0):
                ep = ("model",)
            if not st.tensor_parallel and st.fsdp:
                # fsdp2d: the 'model' axis joins data parallelism — batch
                # over every axis, params fully sharded over both, zero
                # per-layer activation psums. MoE archs keep EP over 'model'
                # (2D-EP: the expert shard_map all-gathers its data-row's
                # tokens over 'model' and reduce-scatters outputs back).
                batch = batch + ("model",)
                if fsdp is not None:
                    fsdp = fsdp + ("model",)
            kv_axes: list[str] = []
            if (mode in ("decode", "prefill") and st.context_parallel_decode
                    and shape is not None):
                if shape.global_batch < _axes_size(mesh, ("data",)):
                    kv_axes.append("data")  # context-parallel cache (long_500k)
                if tp and cfg.n_kv_heads and cfg.n_kv_heads % mesh.shape["model"]:
                    # heads unshardable -> cache LENGTH over 'model' instead
                    # (prefill writes it, flash-decoding style reads it)
                    kv_axes.append("model")
            self.axis_map = {"batch": batch, "seq": seq_shard, "heads": tp,
                             "kv": tuple(kv_axes) or None, "mlp": tp,
                             "vocab": tp, "experts": ep, "fsdp": fsdp,
                             "tp": tp, "expert": ep, "embed_fsdp": fsdp,
                             "seq_cp": tp, "cap": tp}

    # ------------------------------------------------------------------
    # activations
    # ------------------------------------------------------------------
    def axis_size(self, name: str) -> int:
        return self.mesh.shape.get(name, 1)

    def logical_size(self, name: str) -> int:
        return _axes_size(self.mesh, self.axis_map.get(name))

    def spec(self, logical: tuple, shape: tuple | None = None,
             label: str | None = None) -> P:
        parts = []
        used: set = set()
        for i, name in enumerate(logical):
            axes = self.axis_map.get(name) if name else None
            if axes:
                # a mesh axis may appear once per spec: drop re-used axes
                # (e.g. fsdp2d expert weights: dim0 experts->model, dim1
                # fsdp->(data,model) -> dim1 keeps only 'data')
                axes = tuple(a for a in axes if a not in used)
            if axes and shape is not None and shape[i] % _axes_size(self.mesh, axes):
                self._note_drop(label or name, i, axes, shape[i])
                axes = None  # not divisible -> replicate
            if axes:
                parts.append(axes[0] if len(axes) == 1 else tuple(axes))
                used.update(axes)
            else:
                parts.append(None)
        return P(*parts)

    def _note_drop(self, label: str, dim: int, axes: tuple, size: int) -> None:
        key = (label, dim, axes)
        if key in self._drop_seen:
            return
        self._drop_seen.add(key)
        rec = {"label": label, "dim": dim, "axes": list(axes), "size": size,
               "axis_size": _axes_size(self.mesh, axes)}
        self.dropped.append(rec)
        log.warning("sharding drop: %s dim %d (size %d) not divisible by "
                    "mesh axes %s (x%d) -> replicated", label, dim, size,
                    axes, rec["axis_size"])

    def act(self, x: jnp.ndarray, logical: tuple) -> jnp.ndarray:
        s = self.spec(logical, x.shape)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, s))

    def named(self, logical: tuple, shape: tuple | None = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))

    # ------------------------------------------------------------------
    # parameters — path-based rules
    # ------------------------------------------------------------------
    # (regex on 'a/b/c' joined path) -> logical names per dim (trailing dims
    # beyond the rule are replicated). First match wins.
    PARAM_RULES: list[tuple[str, tuple]] = [
        (r"embed/table$", ("vocab", "embed_fsdp")),
        (r"pos_embed/table$", (None, "embed_fsdp")),
        (r"lm_head/kernel$", ("fsdp", "vocab")),
        (r"(q_proj|k_proj|v_proj)/kernel$", ("fsdp", "tp")),
        (r"o_proj/kernel$", ("tp", "fsdp")),
        (r"(up|gate)/kernel$", ("fsdp", "tp")),
        (r"down/kernel$", ("tp", "fsdp")),
        (r"router/kernel$", ("fsdp", None)),
        (r"experts/(gate|up)$", ("expert", "fsdp", "tp")),
        (r"experts/down$", ("expert", "tp", "fsdp")),
        (r"(x_proj|gate_proj|in_proj)/kernel$", ("fsdp", "tp")),
        (r"out_proj/kernel$", ("tp", "fsdp")),
        (r"conv/kernel$", ("tp", None)),
        (r"(a_gate|x_gate)/kernel$", (None, "fsdp", None)),
        (r"dt_proj/kernel$", ("fsdp", "tp")),
        (r"dt_proj/bias$", ("tp",)),
        (r"A_log$", ("tp", None)),
        (r"/(D|lam)$", ("tp",)),
    ]

    def _param_spec(self, path: str, ndim: int, shape: tuple,
                    drop: tuple = ()) -> P:
        # stacked scan blocks carry a leading n_rep dim not covered by rules
        lead: tuple = (None,) if path.startswith("blocks/") else ()
        for pat, logical in self.PARAM_RULES:
            if re.search(pat, path):
                logical = lead + logical
                logical = logical + (None,) * (ndim - len(logical))
                if (path.endswith(("experts/gate", "experts/up", "experts/down"))
                        and self.axis_map.get("expert")):
                    logical = tuple(None if l == "tp" else l for l in logical)
                if drop:
                    logical = tuple(None if l in drop else l for l in logical)
                return self.spec(logical[:ndim], shape)
        return P(*([None] * ndim))  # norms, small vectors

    def params_sharding(self, params_tree: PyTree) -> PyTree:
        def f(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            return NamedSharding(self.mesh,
                                 self._param_spec(pstr, leaf.ndim, leaf.shape))
        return jax.tree_util.tree_map_with_path(f, params_tree)

    def gather_block(self, layer_params: PyTree, compute_dtype) -> PyTree:
        """ZeRO-3-style per-block weight gather: constrain the compute-dtype
        copy of each ≥2D weight to its FSDP-free sharding so XLA all-gathers
        the (small) weights once per block instead of partial-summing (large)
        activations. Paths here are relative to one layer."""
        def f(path, leaf):
            if leaf.ndim < 2:
                return leaf
            pstr = "/".join(_key_str(k) for k in path)
            spec = self._param_spec(pstr, leaf.ndim, leaf.shape,
                                    drop=("fsdp", "embed_fsdp"))
            if "kernel" in pstr or "experts/" in pstr:
                leaf = leaf.astype(compute_dtype)  # A_log/lam etc. stay fp32
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(self.mesh, spec))
        return jax.tree_util.tree_map_with_path(f, layer_params)

    # ------------------------------------------------------------------
    # batches / caches
    # ------------------------------------------------------------------
    def batch_sharding(self, batch_tree: PyTree) -> PyTree:
        def f(leaf):
            logical = ("batch",) + (None,) * (leaf.ndim - 1)
            return self.named(logical, leaf.shape)
        return jax.tree.map(f, batch_tree)

    def cache_sharding(self, cache_tree: PyTree) -> PyTree:
        def f(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            nd = leaf.ndim
            # stacked block caches have a leading n_rep dim
            stacked = "blocks" in pstr
            if re.search(r"(self|cross)/(k|v)$", pstr):
                base = ("batch", "kv", "heads", None)
            elif pstr.endswith("/h"):
                base = ("batch", "mlp")
            elif pstr.endswith("/conv"):
                base = ("batch", None, "mlp")
            else:
                base = ("batch",) + (None,) * 3
            logical = (((None,) + base) if stacked else base)[:nd]
            logical = logical + (None,) * (nd - len(logical))
            return self.named(logical, leaf.shape)
        return jax.tree_util.tree_map_with_path(f, cache_tree)

    def scalar_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # ------------------------------------------------------------------
    # serving (mode="serve"): paged KV block pools
    # ------------------------------------------------------------------
    @property
    def kv_shard(self) -> int:
        """How many ways the paged KV pools shard over 'model' (by KV head).

        1 when the mode is not "serve", the mesh has no model axis, or the
        KV head count does not divide it (divisibility-drop -> replicated).
        """
        if self.mode != "serve" or "model" not in self.mesh.shape:
            return 1
        n = self.mesh.shape["model"]
        kv = self.cfg.n_kv_heads or self.cfg.n_heads
        return n if n > 1 and kv % n == 0 else 1

    def _pool_logical(self, path: str, shape: tuple) -> tuple | None:
        """Logical axes for a paged block-pool leaf, or None if not a pool.

        Pools are ``(n_blocks, page, K, hd)`` (+ a leading n_rep dim for
        scan-stacked blocks); per-row quant scales are ``(n_blocks, page,
        K)``. Both shard dim K ('kv_pool' -> model) by KV head.
        """
        kv = self.cfg.n_kv_heads or self.cfg.n_heads
        lead = ("blocks" in path)
        nd = len(shape) - (1 if lead else 0)
        if nd not in (3, 4) or shape[-1 if nd == 3 else -2] != kv:
            return None
        base = (None, None, "kv_pool") + ((None,) if nd == 4 else ())
        return ((None,) + base) if lead else base

    def serve_cache_sharding(self, cache_tree: PyTree,
                             n_blocks: int) -> PyTree:
        """NamedShardings for a serving cache: block pools (and quant
        scales) sharded by KV head over 'model', everything else (dense
        ring buffers, positions) replicated."""
        def f(path, leaf):
            pstr = "/".join(_key_str(k) for k in path)
            if n_blocks and leaf.ndim >= 3:
                lead = ("blocks" in pstr)
                if leaf.shape[1 if lead else 0] == n_blocks:
                    logical = self._pool_logical(pstr, leaf.shape)
                    if logical is not None:
                        return self.named(logical, leaf.shape)
            return NamedSharding(self.mesh, P(*([None] * leaf.ndim)))
        return jax.tree_util.tree_map_with_path(f, cache_tree)

    def serve_cache_constraint(self, cache_tree: PyTree,
                               shardings: PyTree) -> PyTree:
        """Pin a cache pytree to its serve shardings inside a jitted graph
        so donation keeps a stable layout across engine steps."""
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            cache_tree, shardings)

    def serve_kv_scope(self):
        """Context manager advertising the sharded pool layout to the kernel
        registry (read by the sharded ``paged_attention`` ``supports()``).
        No-op (null context) when the pools are replicated."""
        import contextlib

        from repro.kernels import dispatch as kdispatch
        if self.kv_shard <= 1:
            return contextlib.nullcontext()
        return kdispatch.serve_mesh_scope(self.mesh, "model")


def _key_str(k) -> str:
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)
