"""Chip peaks and the work a served call needs, counted from shapes.

The counts are of the work the algorithm needs, whatever implements it:
every weight read once, each running sequence's valid K/V read once, one
K/V slot written per sequence per layer, and the matmul FLOPs of the
tokens actually served. Padded batch rows, padded prefill tokens and a
cache write that touches more than one slot are waste, not work.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}

BYTES = 2  # bf16 weights, activations and cache


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; an unknown kind is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a dense decoder that the counts need."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    gated: bool
    vocab: int
    tied: bool

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.heads + 2 * self.kv_heads)
        ffn = d * self.d_ff * (3 if self.gated else 2)
        return attn + ffn

    @property
    def weight_params(self) -> int:
        """Every parameter: layers, norms, embedding and head."""
        per_layer = self.layer_matmul_params + 2 * self.d_model
        head = 0 if self.tied else self.d_model * self.vocab
        return (self.layers * per_layer + self.d_model
                + self.vocab * self.d_model + head)

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * 2 * self.kv_heads * self.head_dim * BYTES


def decode_work(s: Shape, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode call needs for running sequences whose
    caches hold ``contexts`` tokens before the call."""
    contexts = list(contexts)
    n = len(contexts)
    if n == 0:
        return 0.0, 0.0
    ctx = float(sum(contexts))
    per_token = 2.0 * (s.layers * s.layer_matmul_params
                       + s.d_model * s.vocab)
    # QK^T and PV over every valid key, the new one included
    attn = 4.0 * s.layers * s.heads * s.head_dim * (ctx + n)
    flops = n * per_token + attn
    # the head is read once; only the served embedding rows are read
    head_bytes = s.d_model * s.vocab * BYTES
    embed_rows = 0 if s.tied else n * s.d_model * BYTES
    layer_bytes = s.layers * (s.layer_matmul_params
                              + 2 * s.d_model) * BYTES
    kv_read = ctx * s.kv_bytes_per_token
    kv_write = n * s.kv_bytes_per_token
    nbytes = (layer_bytes + s.d_model * BYTES + head_bytes + embed_rows
              + kv_read + kv_write)
    return flops, float(nbytes)


def prefill_flops(s: Shape, tokens: int) -> float:
    """Model FLOPs of a causal forward over ``tokens`` prompt tokens,
    logits at every position included."""
    per_token = 2.0 * (s.layers * s.layer_matmul_params
                       + s.d_model * s.vocab)
    attn = 4.0 * s.layers * s.heads * s.head_dim * tokens * (tokens + 1) / 2
    return tokens * per_token + attn


def least_time(flops: float, nbytes: float, device_kind: str) -> float:
    """Seconds the chip needs at least: the larger of the compute and the
    memory bound."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
