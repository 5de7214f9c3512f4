"""Unified kernel-backend registry: capability-negotiating op dispatch.

The paper's layered-openness thesis (one ISA surface, many implementations —
Occamy's 8-to-64-bit multi-precision FPU; Occamy -> Ramora -> Ogopogo swapping
interconnect layers under an unchanged programming model) applied to the
software stack: every hot-spot op (``gemm``, ``flash_attention``, ``lru_scan``,
``packed_gather_rows``, ``instream_scale_reduce``, ...) is a *name* in an
``OpRegistry``; concrete kernels register against that name with a
``supports(request)`` capability predicate and a priority. Call sites never
pick an implementation — they dispatch through the registry, which negotiates:

  1. Resolve the active :class:`Backend` — an explicit ``backend=`` argument,
     the innermost :func:`use_backend` context, the ``REPRO_KERNEL_BACKEND``
     environment variable, or auto-detection from ``jax.default_backend()``
     (TPU -> ``pallas``, anything else -> ``ref``).
  2. Walk the op's implementations in priority order, keeping those that list
     the active backend and whose ``supports`` predicate accepts the request's
     shapes/dtypes/platform/params.
  3. Fall back to the universal ``ref`` oracle when no kernel can serve the
     request (GQA head counts the kernel layout can't express, tiny dims, ...)
     — unsupported shapes *negotiate down*, they never error.

Block/tile sizes live in a per-op tuning table keyed by (op, shape bucket),
overridable per scope (``use_backend(blocks=...)``) or per distribution
strategy (``StrategyConfig.kernel_blocks``). Adding a backend, an op variant,
or per-shape tuning is a registry entry — not a cross-cutting edit.
"""
from __future__ import annotations

import collections
import contextlib
import contextvars
import inspect
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Mapping

import jax

__all__ = [
    "BACKENDS", "Backend", "BlockSpec", "KERNEL_BACKENDS", "OpImpl",
    "OpRequest", "OpRegistry", "blocks_from_pairs", "default_backend_name",
    "kernel_scope_active", "negotiated_model_backend", "registry",
    "requested_backend", "resolve_backend", "serve_mesh", "serve_mesh_scope",
    "spmd_xla_scope", "use_backend",
]

#: Valid backend names. ``ref`` is the pure-jnp oracle, ``interpret`` runs the
#: Pallas kernels through the interpreter (CPU validation), ``pallas`` is the
#: compiled TPU path. ``auto`` (accepted everywhere a name is) resolves per
#: platform.
BACKENDS = ("ref", "interpret", "pallas")

_ENV_VAR = "REPRO_KERNEL_BACKEND"


# --------------------------------------------------------------------------
# backend resolution
# --------------------------------------------------------------------------
#: Backends that execute the Pallas kernels (vs the jnp oracle).
KERNEL_BACKENDS = ("interpret", "pallas")


@dataclass(frozen=True)
class Backend:
    """A resolved execution backend for kernel dispatch."""
    name: str                     # ref | interpret | pallas
    platform: str                 # jax.default_backend(): cpu | gpu | tpu

    @property
    def interpret(self) -> bool:
        return self.name == "interpret"

    @property
    def compiled_available(self) -> bool:
        """Whether compiled (non-interpreted) Pallas kernels can run here."""
        return self.platform == "tpu"


_active: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "repro_kernel_backend", default=None)
_block_overrides: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_kernel_blocks", default=())


def default_backend_name() -> str:
    """Platform-derived default: compiled kernels on TPU, oracle elsewhere.

    ``REPRO_KERNEL_BACKEND`` overrides (used by CI to force ``interpret``)."""
    env = os.environ.get(_ENV_VAR, "").strip()
    if env and env != "auto":
        return env
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _validate(name: str) -> None:
    if name not in BACKENDS and name != "auto":
        raise ValueError(
            f"unknown kernel backend {name!r}; expected one of "
            f"{BACKENDS + ('auto',)}")


def resolve_backend(name: str | None = None) -> Backend:
    """Explicit arg > ``use_backend`` context > env var / platform auto."""
    n = name or _active.get() or "auto"
    _validate(n)
    if n == "auto":
        n = default_backend_name()
        _validate(n)
    return Backend(n, jax.default_backend())


def requested_backend() -> str | None:
    """The innermost *explicitly requested* backend (``use_backend`` scope),
    or None. Model layers use this: platform auto-detection alone must not
    reroute a training graph through a forward-only kernel path."""
    return _active.get()


def kernel_scope_active() -> bool:
    """True inside an explicit ``use_backend`` scope that selects the Pallas
    kernels — ``auto`` included where the platform resolves it to them (a
    TPU). The one predicate model call sites (dense, MoE gather, diag_scan)
    gate on — ambient auto-detection without a scope never flips it."""
    be = requested_backend()
    if be == "auto":
        be = negotiated_model_backend(be)
    return be in KERNEL_BACKENDS


def spmd_xla_scope():
    """Scope for partitioned (SPMD) model graphs: neutralizes any enclosing
    kernel scope so no ``pallas_call`` is traced inside pjit — a raw kernel
    on sharded activations would need shard_map. Sharded graphs keep the XLA
    collectives-aware paths; the model entry points (``forward`` /
    ``decode_step``) apply this whenever a partitioner is in play."""
    if kernel_scope_active():
        return use_backend("ref")
    return contextlib.nullcontext()


_serve_mesh: contextvars.ContextVar[tuple | None] = contextvars.ContextVar(
    "repro_serve_mesh", default=None)


@contextlib.contextmanager
def serve_mesh_scope(mesh, axis: str):
    """Advertise a sharded serving layout to ``supports()`` predicates.

    Opened (at trace time) by the model layer around registry dispatches
    whose pool operands are sharded over ``mesh`` axis ``axis`` — e.g. the
    paged KV block pools sharded by KV head. Implementations that can run
    the op under ``shard_map`` on that layout key their ``supports()`` off
    :func:`serve_mesh`; everything else sees the operands as global arrays
    and negotiation falls through to the local/ref paths unchanged.
    """
    tok = _serve_mesh.set((mesh, axis))
    try:
        yield
    finally:
        _serve_mesh.reset(tok)


def serve_mesh() -> tuple | None:
    """The active ``(mesh, axis)`` serving layout, or None outside a
    :func:`serve_mesh_scope`."""
    return _serve_mesh.get()


def negotiated_model_backend(cfg_backend: str) -> str | None:
    """Backend a model layer should route its kernels through, or None for
    the default XLA path. A ``use_backend`` scope wins over the config field;
    ``auto`` only opts in on TPU (the CPU/GPU production path stays XLA)."""
    be = requested_backend() or cfg_backend or None
    if not be:
        return None
    _validate(be)
    if be == "auto":
        return "pallas" if jax.default_backend() == "tpu" else None
    return be


@contextlib.contextmanager
def use_backend(name: str | None = None, *,
                blocks: Mapping[Any, Mapping[str, int]] | None = None):
    """Context-scoped backend and/or block-size override.

        with use_backend("interpret"):
            y = ops.gemm(x, w)                  # Pallas kernel, interpreted
        with use_backend(blocks={"gemm": {"block_m": 64}}):
            y = ops.gemm(x, w)                  # default backend, tuned tiles

    ``blocks`` keys are an op name (all shape buckets) or ``(op, bucket)``;
    values map kernel tile kwargs to sizes. Scopes nest; the innermost wins.
    Yields the resolved :class:`Backend`.

    The scope is read at *trace* time and is not part of any jit cache key:
    a scope around a ``jax.jit`` function that already traced reuses the
    cached executable unchanged. Open the scope around the *first* call (as
    ``ServeEngine`` does, pinning one backend for its lifetime), or keep the
    jit inside the scope.
    """
    if name is not None:
        _validate(name)
    tok = _active.set(name) if name is not None else None
    btok = (_block_overrides.set(_block_overrides.get() + (dict(blocks),))
            if blocks else None)
    try:
        yield resolve_backend(name)
    finally:
        if btok is not None:
            _block_overrides.reset(btok)
        if tok is not None:
            _active.reset(tok)


def blocks_from_pairs(pairs: Iterable) -> dict:
    """Decode ``StrategyConfig.kernel_blocks`` — a hashable tuple of
    ``(op, bucket, ((name, size), ...))`` entries (bucket ``"*"`` = any) —
    into the mapping form ``use_backend(blocks=...)`` takes."""
    out: dict = {}
    for op, bucket, sizes in pairs:
        key = op if bucket in ("*", None) else (op, bucket)
        out[key] = dict(sizes)
    return out


# --------------------------------------------------------------------------
# requests, capabilities, block tuning
# --------------------------------------------------------------------------
@dataclass(frozen=True)
class OpRequest:
    """What a call site is asking for: shapes/dtypes of the array operands,
    the target platform, and the static op params. ``supports`` predicates
    and shape-bucket functions see exactly this."""
    op: str
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[str, ...]
    platform: str
    params: tuple[tuple[str, Any], ...] = ()

    def param(self, key: str, default: Any = None) -> Any:
        return dict(self.params).get(key, default)

    @property
    def max_dim(self) -> int:
        return max((d for s in self.shapes for d in s), default=0)

    def floating(self) -> bool:
        return all(("float" in d) or ("bf16" in d) for d in self.dtypes)


@dataclass(frozen=True)
class BlockSpec:
    """Per-op tile-size bundle: kernel kwarg name -> size. (Distinct from
    ``pl.BlockSpec`` — this is the *tuning table entry* that ends up as the
    kernel wrapper's ``block_*`` keyword arguments.)"""
    sizes: tuple[tuple[str, int], ...] = ()

    @classmethod
    def of(cls, **sizes: int) -> "BlockSpec":
        return cls(tuple(sorted(sizes.items())))

    def asdict(self) -> dict[str, int]:
        return dict(self.sizes)


@dataclass(frozen=True)
class OpImpl:
    """One registered implementation of an op."""
    op: str
    name: str
    fn: Callable
    backends: frozenset[str]
    supports: Callable[[OpRequest], bool] | None = None
    priority: int = 0
    pass_interpret: bool = False  # fn takes interpret= from the backend

    def accepts(self, req: OpRequest) -> bool:
        return self.supports is None or bool(self.supports(req))


def _default_bucket(req: OpRequest) -> str:
    """Coarse shape bucket: pad-friendly small tiles below one MXU-ish edge,
    full 128-multiples above."""
    return "small" if req.max_dim <= 256 else "large"


class OpRegistry:
    """Name -> prioritized implementations + block-size tuning table."""

    def __init__(self):
        self._impls: dict[str, list[OpImpl]] = {}
        self._blocks: dict[tuple[str, str], BlockSpec] = {}
        self._bucket_fns: dict[str, Callable[[OpRequest], str]] = {}
        self._sig_cache: dict[Callable, tuple[frozenset[str], bool]] = {}
        self._resolved: collections.Counter = collections.Counter()

    # ---- registration ----------------------------------------------------
    def register(self, op: str, name: str, *, backends: Iterable[str],
                 supports: Callable[[OpRequest], bool] | None = None,
                 priority: int = 0, pass_interpret: bool = False):
        """Decorator: register ``fn`` as implementation ``name`` of ``op``.

        ``backends`` lists the backend names this impl can serve. A kernel
        impl typically registers ``("pallas", "interpret")`` with
        ``pass_interpret=True`` (it receives ``interpret=`` from the resolved
        backend); the oracle registers all three backends at priority 0 so it
        doubles as the negotiation fallback.
        """
        bset = frozenset(backends)
        unknown = bset - set(BACKENDS)
        if unknown:
            raise ValueError(f"unknown backends {sorted(unknown)} for {op}")

        def deco(fn):
            entry = OpImpl(op=op, name=name, fn=fn, backends=bset,
                           supports=supports, priority=priority,
                           pass_interpret=pass_interpret)
            impls = self._impls.setdefault(op, [])
            impls[:] = [e for e in impls if e.name != name] + [entry]
            impls.sort(key=lambda e: -e.priority)
            return fn

        return deco

    def register_blocks(self, op: str, bucket: str, **sizes: int) -> None:
        """Default tile sizes for (op, shape bucket); bucket "*" = any."""
        self._blocks[(op, bucket)] = BlockSpec.of(**sizes)

    def set_bucket_fn(self, op: str, fn: Callable[[OpRequest], str]) -> None:
        self._bucket_fns[op] = fn

    # ---- introspection ---------------------------------------------------
    def ops(self) -> list[str]:
        return sorted(self._impls)

    def implementations(self, op: str) -> list[OpImpl]:
        return list(self._impls.get(op, ()))

    def request(self, op: str, *args, **params) -> OpRequest:
        """Build the OpRequest ``dispatch`` would see (introspection/tests)."""
        platform = jax.default_backend()
        shapes = tuple(tuple(a.shape) for a in args if hasattr(a, "shape"))
        dtypes = tuple(str(a.dtype) for a in args if hasattr(a, "dtype"))
        static = tuple(sorted((k, v) for k, v in params.items()
                              if isinstance(v, (int, float, str, bool,
                                                type(None)))))
        return OpRequest(op, shapes, dtypes, platform, static)

    def resolutions(self) -> dict[tuple[str, str], int]:
        """How often each ``(op, impl)`` was chosen by :meth:`dispatch`.

        Counted when ``dispatch`` runs, which inside ``jax.jit`` is trace
        time: a cached executable adds nothing. So the record says which
        impl each traced program holds — a kernel, or the ``ref`` oracle it
        negotiated down to."""
        return dict(self._resolved)

    def clear_resolutions(self) -> None:
        self._resolved.clear()

    def describe(self) -> str:
        lines = []
        for op in self.ops():
            impls = ", ".join(
                f"{e.name}[{'/'.join(sorted(e.backends))}] p{e.priority}"
                for e in self._impls[op])
            lines.append(f"{op}: {impls}")
        return "\n".join(lines)

    # ---- negotiation -----------------------------------------------------
    def select(self, op: str, req: OpRequest, backend: Backend) -> OpImpl:
        """Highest-priority impl serving ``backend`` that supports ``req``;
        negotiates down to the ``ref`` oracle instead of erroring. A
        ``pallas`` backend on a platform with no compiled kernels (CPU/GPU)
        treats every kernel impl as unsupported — pinning ``pallas`` on a
        dev box falls back to the oracle rather than crashing in
        ``pallas_call``."""
        impls = self._impls.get(op)
        if not impls:
            raise KeyError(f"no implementations registered for op {op!r}")
        for entry in impls:
            if (entry.pass_interpret and backend.name == "pallas"
                    and not backend.compiled_available):
                continue
            if backend.name in entry.backends and entry.accepts(req):
                return entry
        for entry in impls:  # negotiate down: the universal oracle
            if "ref" in entry.backends and entry.accepts(req):
                return entry
        raise NotImplementedError(
            f"op {op!r}: no implementation supports {req} on backend "
            f"{backend.name!r} and no ref fallback is registered")

    def blocks_for(self, op: str, req: OpRequest) -> dict[str, int]:
        """Tuning-table tile sizes for this request: (op, "*") then
        (op, bucket) defaults, then context/strategy overrides, innermost
        last (later wins)."""
        bucket = self._bucket_fns.get(op, _default_bucket)(req)
        out: dict[str, int] = {}
        for key in ((op, "*"), (op, bucket)):
            if key in self._blocks:
                out.update(self._blocks[key].asdict())
        for scope in _block_overrides.get():
            for key in (op, (op, bucket)):
                if key in scope:
                    out.update(scope[key])
        return out

    # ---- dispatch --------------------------------------------------------
    def _signature(self, fn: Callable) -> tuple[frozenset[str], bool]:
        if fn not in self._sig_cache:
            sig = inspect.signature(fn)
            names = frozenset(
                p.name for p in sig.parameters.values()
                if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY))
            var_kw = any(p.kind == p.VAR_KEYWORD
                         for p in sig.parameters.values())
            self._sig_cache[fn] = (names, var_kw)
        return self._sig_cache[fn]

    def _op_kwargs(self, op: str) -> frozenset[str]:
        """Union of kwarg names accepted by any of the op's impls."""
        names: set[str] = {"interpret"}
        for entry in self._impls.get(op, ()):
            names |= self._signature(entry.fn)[0]
        return frozenset(names)

    def dispatch(self, op: str, *args, backend: str | None = None, **kwargs):
        """The one negotiation path every public op flows through."""
        be = resolve_backend(backend)
        req = self.request(op, *args, **kwargs)
        impl = self.select(op, req, be)
        self._resolved[(op, impl.name)] += 1
        # typo'd kwargs must fail loudly, as the pre-registry jitted ops did;
        # only *tuning-table defaults* are filtered per-impl below (the ref
        # oracle legitimately ignores the kernel's tile sizes)
        unknown = set(kwargs) - self._op_kwargs(op)
        if unknown:
            raise TypeError(
                f"op {op!r}: unknown keyword argument(s) {sorted(unknown)}; "
                f"accepted: {sorted(self._op_kwargs(op))}")
        call_kw = dict(self.blocks_for(op, req))
        call_kw.update(kwargs)
        if impl.pass_interpret:
            call_kw["interpret"] = be.interpret
        names, var_kw = self._signature(impl.fn)
        if not var_kw:
            call_kw = {k: v for k, v in call_kw.items() if k in names}
        # the chosen impl shows up by name in profiler timelines (Perfetto /
        # jax.profiler), so a trace answers "which kernel actually ran?"
        with jax.named_scope(f"repro.{op}.{impl.name}"):
            return impl.fn(*args, **call_kw)


#: Process-wide registry. ``repro.kernels.ops`` populates it at import.
registry = OpRegistry()
