"""Qwen3 (``model_type`` ``qwen3``): the Llama decoder of
:mod:`bench.arch.llama` with an RMSNorm over each query and key head before
the rotary positions (weights ``q_norm`` and ``k_norm``, ``(layers,
head_dim)``)."""
from __future__ import annotations

import functools

from bench.arch import llama

program_config = functools.partial(llama.program_config, qk_norm=True)
weight_shapes = functools.partial(llama.weight_shapes, qk_norm=True)
to_program = llama.to_program
shape = functools.partial(llama.shape, qk_norm=True)
hidden = functools.partial(llama.hidden, qk_norm=True)
