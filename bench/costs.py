"""The yardstick's arithmetic: chip peaks, and the operations and bytes that
the served model needs, computed from its published sizes.

Everything here is the benchmark's own, so a change to the program cannot
move it. Counts follow the model's equations, not what the program happens
to execute: a kernel that reads more than it needs, or computes rows that no
request uses, shows as a lower share of its roofline.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s per chip
    hbm_bw: float            # bytes/s per chip
    source: str


#: Published peaks keyed by ``jax.Device.device_kind``. A kind not listed is
#: an error: a benchmark never borrows another chip's peak.
PEAKS: dict[str, Peaks] = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12, hbm_bw=819e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peaks(device_kind: str) -> Peaks:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table entry for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


@dataclass(frozen=True)
class Shape:
    """The sizes the arithmetic needs, read from a configuration file."""
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    qk_norm: bool = False
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shape":
        return cls(n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   n_heads=c["num_attention_heads"],
                   n_kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or (c["hidden_size"]
                                                  // c["num_attention_heads"]),
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=bool(c["tie_word_embeddings"]),
                   qk_norm=c.get("model_type") == "qwen3")

    # ---- parameters -------------------------------------------------------
    @property
    def attn_params(self) -> int:
        """q, k, v and o projections of one layer."""
        d, hd = self.d_model, self.head_dim
        return d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)

    @property
    def mlp_params(self) -> int:
        """gate, up and down projections of one layer."""
        return 3 * self.d_model * self.d_ff

    @property
    def layer_matmul_params(self) -> int:
        return self.attn_params + self.mlp_params

    @property
    def params(self) -> int:
        """Every parameter, norms included; a tied head counts once."""
        d, hd, L = self.d_model, self.head_dim, self.n_layers
        norms = L * (2 * d + (2 * hd if self.qk_norm else 0)) + d
        head = 0 if self.tied else d * self.vocab
        return L * self.layer_matmul_params + self.vocab * d + head + norms

    @property
    def weight_bytes(self) -> int:
        return self.params * self.dtype_bytes

    # ---- KV ---------------------------------------------------------------
    @property
    def kv_row_bytes(self) -> int:
        """K and V of one token in one layer."""
        return 2 * self.n_kv_heads * self.head_dim * self.dtype_bytes

    @property
    def kv_token_bytes(self) -> int:
        return self.n_layers * self.kv_row_bytes

    # ---- operations -------------------------------------------------------
    def token_flops(self, context: int, logits: bool) -> float:
        """Model FLOPs of one token that attends to ``context`` positions
        (itself included): every matmul of every layer, QK^T and PV over the
        context, and the vocabulary projection where its logits are used."""
        L, H, hd = self.n_layers, self.n_heads, self.head_dim
        f = 2.0 * L * self.layer_matmul_params + 4.0 * L * H * hd * context
        if logits:
            f += 2.0 * self.d_model * self.vocab
        return f

    def prefill_flops(self, start: int, end: int) -> float:
        """Model FLOPs of prefilling positions ``start..end-1`` (position p
        attends to p + 1 positions); only the last position's logits are
        used."""
        n = end - start
        if n <= 0:
            return 0.0
        L, H, hd = self.n_layers, self.n_heads, self.head_dim
        ctx = (start + 1 + end) * n / 2.0          # sum of p + 1
        return (2.0 * L * self.layer_matmul_params * n
                + 4.0 * L * H * hd * ctx + 2.0 * self.d_model * self.vocab)

    def gemm(self, m: int, k: int, n: int) -> tuple[float, float]:
        """(FLOPs, least HBM bytes) of one (m, k) x (k, n) bf16 matmul:
        each operand read once, the result written once."""
        b = self.dtype_bytes
        return 2.0 * m * k * n, float(b * (m * k + k * n + m * n))

    def paged_attention(self, kv_rows: int) -> tuple[float, float]:
        """(FLOPs, least bytes) of decode attention over ``kv_rows`` context
        rows in one layer: each row's K and V read once."""
        G = self.n_heads // self.n_kv_heads
        flops = 4.0 * kv_rows * self.n_kv_heads * G * self.head_dim
        return flops, float(kv_rows * self.kv_row_bytes)


def least_time(flops: float, nbytes: float, pk: Peaks) -> float:
    """The roofline: the larger of compute time and memory time."""
    return max(flops / pk.bf16_flops, nbytes / pk.hbm_bw)
