"""One module per published architecture, found by the configuration's
``model_type``: ``bench/arch/<model_type>.py``.

A module gives, for a configuration ``c`` of its architecture:

- ``program_config(c)``: the program's ``ModelConfig``;
- ``weight_shapes(c)``: ``{name: shape}`` of the benchmark's own weight
  layout, which :func:`bench.model.make_weights` draws (``*norm`` ones,
  ``embed`` with fan-in ``d_model``, every other name with fan-in
  ``shape[-2]``);
- ``to_program(w, c)``: the program's parameter tree over the same arrays;
- ``shape(c)``: the cost object: ``vocab``, ``n_layers``, ``weight_bytes``,
  ``kv_token_bytes``, ``token_flops(context, logits)``,
  ``prefill_flops(start, end)``, ``context_flops(rows)`` (attention FLOPs
  of ``rows`` context rows over all layers), and what the readers of its
  kernels need;
- ``hidden(w, c, tokens, quant)``: the float32 reference's final hidden
  states of one sequence, every weight matrix first rounded to int8 where
  ``quant`` is set.

The head (``lm_head``, else the embedding) and the gaps are
:mod:`bench.reference`'s. A new architecture is a new file here.
"""
from __future__ import annotations

import importlib
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent

#: modules loaded from another root, each once, so that its jitted
#: reference keeps its compiled programs across calls
_by_path: dict[Path, object] = {}


def known(where: Path) -> list[str]:
    """The architectures that have a module under ``where``."""
    return sorted(p.stem for p in Path(where).glob("*.py")
                  if p.stem != "__init__")


def load(c: dict, root: Path | None = None):
    """The module of ``c["model_type"]`` under ``<root>/bench/arch`` (this
    directory where ``root`` is not given)."""
    where = HERE if root is None else (Path(root) / "bench" / "arch").resolve()
    name = c["model_type"]
    path = where / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no architecture module for model_type {name!r} "
                       f"under {where} (known: {known(where)})")
    if where == HERE:
        return importlib.import_module(f"{__name__}.{name}")
    if path not in _by_path:
        spec = importlib.util.spec_from_file_location(
            f"bench_arch_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _by_path[path] = mod
    return _by_path[path]
