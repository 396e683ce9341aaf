"""Chip peaks and the work a served call needs, counted from shapes.

The counts are of the work the algorithm needs, whatever implements it:
every weight read once, each running sequence's valid cache read once,
one cache slot written per sequence per layer, and the matmul FLOPs of
the tokens actually served (of a layer with experts, the experts each
token is routed to). Padded batch rows, padded prefill tokens and a
cache write that touches more than one slot are waste, not work.

Of a layer with routed experts, only the experts that the served tokens
reach need to be read, and how many that is depends on the routing,
which the counts do not see. They count the fewest any routing reaches,
``experts_per_token``, so a share of a roofline never reads above the
real one, whatever the routing and the dispatch.

Latent attention decodes in the absorbed form, attending over the cached
latent; its prefill attends per head.
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
    """The sizes of a decoder that the counts need. The optional fields
    default to a dense decoder with grouped-query attention; set, they
    add routed and shared experts (``experts``), leading dense layers
    before them (``dense_prefix``) and latent attention
    (``kv_lora_rank``)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    gated: bool
    vocab: int
    tied: bool
    dense_prefix: int = 0
    experts: int = 0
    experts_per_token: int = 0
    shared_experts: int = 0
    expert_ff: int = 0
    kv_lora_rank: int = 0
    qk_rope_dim: int = 0
    qk_nope_dim: int = 0
    v_head_dim: int = 0

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def moe_layers(self) -> int:
        """Layers whose FFN is experts; the others run the dense FFN."""
        return self.layers - self.dense_prefix if self.experts else 0

    @property
    def attn_params(self) -> int:
        """One layer's attention matmul parameters."""
        d, h = self.d_model, self.heads
        if not self.mla:
            return d * self.head_dim * (2 * h + 2 * self.kv_heads)
        r, rope = self.kv_lora_rank, self.qk_rope_dim
        nope, v = self.qk_nope_dim, self.v_head_dim
        return (d * h * (nope + rope) + d * (r + rope) + r * h * nope
                + r * h * v + h * v * d)

    @property
    def layer_norm_params(self) -> int:
        """One layer's norm weights: two RMSNorms, and latent
        attention's norm of the latent."""
        return 2 * self.d_model + self.kv_lora_rank

    @property
    def expert_params(self) -> int:
        """One routed or shared expert: a SwiGLU FFN of ``expert_ff``."""
        return 3 * self.d_model * self.expert_ff

    @property
    def moe_fixed_params(self) -> int:
        """The parts of a MoE layer that every token reads: the router
        and the shared experts."""
        return (self.d_model * self.experts
                + self.shared_experts * self.expert_params)

    @property
    def layer_matmul_params(self) -> int:
        """A dense layer's matmul parameters: attention and the FFN of
        ``d_ff``."""
        ffn = self.d_model * self.d_ff * (3 if self.gated else 2)
        return self.attn_params + ffn

    def _layers_params(self, routed) -> int:
        """Matmul parameters of every layer, ``routed`` routed experts
        counted in each layer with experts."""
        moe = (self.attn_params + self.moe_fixed_params
               + routed * self.expert_params)
        return ((self.layers - self.moe_layers) * self.layer_matmul_params
                + self.moe_layers * moe)

    @property
    def weight_params(self) -> int:
        """Every parameter: layers, norms, embedding and head."""
        head = 0 if self.tied else self.d_model * self.vocab
        return (self._layers_params(self.experts)
                + self.layers * self.layer_norm_params + self.d_model
                + self.vocab * self.d_model + head)

    @property
    def kv_bytes_per_token(self) -> int:
        """Cache bytes of one token over every layer: K and V of each KV
        head, or the latent and the shared rotary key."""
        if self.mla:
            return self.layers * (self.kv_lora_rank + self.qk_rope_dim) \
                * BYTES
        return self.layers * 2 * self.kv_heads * self.head_dim * BYTES

    @property
    def token_matmul_params(self) -> int:
        """Matmul parameters one token runs through: in a layer with
        experts, the router, the shared experts and the
        ``experts_per_token`` it is routed to; the head."""
        return (self._layers_params(self.experts_per_token)
                + self.d_model * self.vocab)

    @property
    def layer_bytes(self) -> int:
        """Bytes of the layers' weights that a call serving one token or
        more reads at least: every dense weight once, and in a layer with
        experts the router, the shared experts and ``experts_per_token``
        routed experts."""
        return (self._layers_params(self.experts_per_token)
                + self.layers * self.layer_norm_params) * BYTES

    @property
    def decode_attn_flops_per_key(self) -> int:
        """FLOPs of one query against one cached key over every layer:
        QK^T and PV per head, and for latent attention in the absorbed
        form, over the latent and the rotary key."""
        h = self.heads
        if self.mla:
            return self.layers * 2 * h * (2 * self.kv_lora_rank
                                          + self.qk_rope_dim)
        return self.layers * 2 * h * 2 * self.head_dim

    @property
    def prefill_attn_flops_per_pair(self) -> int:
        """FLOPs of one query-key pair over every layer in the per-head
        form: QK^T over the query-key width, PV over the value width."""
        h = self.heads
        if self.mla:
            width = self.qk_nope_dim + self.qk_rope_dim + self.v_head_dim
            return self.layers * 2 * h * width
        return self.layers * 2 * h * 2 * self.head_dim


def decode_work(s: Shape, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) one decode call needs for running sequences whose
    caches hold ``contexts`` tokens before the call."""
    contexts = list(contexts)
    n = len(contexts)
    if n == 0:
        return 0.0, 0.0
    ctx = float(sum(contexts))
    # every valid key, the new one included
    flops = n * 2.0 * s.token_matmul_params \
        + s.decode_attn_flops_per_key * (ctx + n)
    # the head is read once; only the served embedding rows are read
    head_bytes = s.d_model * s.vocab * BYTES
    embed_rows = 0 if s.tied else n * s.d_model * BYTES
    kv_read = ctx * s.kv_bytes_per_token
    kv_write = n * s.kv_bytes_per_token
    nbytes = (s.layer_bytes + s.d_model * BYTES + head_bytes
              + embed_rows + kv_read + kv_write)
    return flops, float(nbytes)


def prefill_flops(s: Shape, tokens: int) -> float:
    """Model FLOPs of a causal forward over ``tokens`` prompt tokens,
    logits at every position included."""
    attn = s.prefill_attn_flops_per_pair * tokens * (tokens + 1) / 2
    return tokens * 2.0 * s.token_matmul_params + attn


def least_time(flops: float, nbytes: float, device_kind: str) -> float:
    """Seconds the chip needs at least: the larger of the compute and the
    memory bound."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
