"""Shared model-definition machinery: config dataclass, norms, rope, inits.

All models are pure-JAX pytree-param modules (no flax): ``init_*`` functions
build nested dicts of arrays, ``apply``-style functions consume them. Layer
stacks are stored with a leading ``layer`` axis and executed with
``jax.lax.scan`` so the traced graph (and XLA compile time) stays small even
for 48-layer multi-billion-parameter configs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree of jnp arrays


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture hyper-parameters for every supported family."""

    name: str = "model"
    arch_type: str = "dense"  # dense | moe | ssm | hybrid | encdec | vlm
    num_layers: int = 2
    d_model: int = 256
    num_heads: int = 4
    num_kv_heads: int = 4
    head_dim: int = 64
    d_ff: int = 512
    vocab_size: int = 1024

    # ffn / norm flavour
    ffn_activation: str = "swiglu"  # swiglu | squared_relu | gelu
    use_qk_norm: bool = False       # chameleon-style qk layernorm
    norm_eps: float = 1e-6

    # positional encoding
    use_rope: bool = True
    rope_theta: float = 10000.0

    # attention variants
    attention_window: int = 0       # 0 = full attention; >0 = sliding window

    # MoE
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 1
    moe_d_ff: int = 0               # per-expert hidden dim (deepseek style)
    first_k_dense: int = 0          # leading dense layers (deepseek)
    router_jitter: float = 0.0

    # MLA (deepseek)
    use_mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    # SSM (mamba2 / SSD)
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 64
    ssm_ngroups: int = 1
    conv_kernel: int = 4

    # hybrid (recurrentgemma / griffin)
    block_pattern: Tuple[str, ...] = ()   # cycled over layers, e.g. ("rec","rec","attn")
    lru_width: int = 0
    local_window: int = 0

    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_seq: int = 1500          # whisper frame count after conv frontend

    # modality frontend stub (vlm/audio): if set, inputs may be embeddings
    frontend_stub: str = ""          # "" | "audio_frames" | "vq_image_tokens"

    # numerics
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    tie_embeddings: bool = False

    # execution
    use_pallas: bool = False         # True: Pallas kernels (TPU / interpret)
    remat: bool = True               # checkpoint layer bodies in training
    # Full-sequence attention reference path: "naive" materializes the SxS
    # score matrix (baseline; what the Pallas kernel replaces on TPU);
    # "chunked" streams KV blocks with a running softmax (flash-style jnp) —
    # §Perf iteration 1, bounded temps for 32k prefill.
    ref_attention: str = "naive"
    # MoE dispatch: "dense" (einsum over ALL experts — baseline, E/top_k
    # FLOPs waste) or "capacity" (scatter/gather per-expert buffers — §Perf
    # compute-term optimization).
    moe_dispatch: str = "dense"
    capacity_factor: float = 1.25
    # apply an explicit expert-parallel sharding constraint to the capacity
    # dispatch buffers (GSPMD cannot propagate sharding through the
    # data-dependent scatter; requires an active mesh context)
    moe_ep_constraint: bool = False
    # Unroll layer stacks instead of lax.scan. Used by the roofline cost
    # extrapolation: XLA cost_analysis counts a scan body ONCE regardless of
    # trip count, so exact per-layer FLOPs/bytes come from compiling small
    # unrolled variants (see launch/dryrun.py --cost-extrapolate).
    unroll_layers: bool = False

    # provenance
    source: str = ""                 # citation per assignment

    # ------------------------------------------------------------------
    @property
    def activation_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.dtype)

    @property
    def weight_dtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)

    @property
    def q_per_kv(self) -> int:
        return max(1, self.num_heads // max(1, self.num_kv_heads))

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant: same family, tiny dims (spec: <=2 layers,
        d_model<=512, <=4 experts)."""
        kw = dict(
            num_layers=2,
            d_model=min(self.d_model, 256),
            num_heads=4,
            num_kv_heads=min(4, max(1, self.num_kv_heads)),
            head_dim=64,
            d_ff=min(self.d_ff, 512),
            vocab_size=min(self.vocab_size, 512),
            dtype="float32",
            param_dtype="float32",
        )
        if self.num_experts:
            kw.update(num_experts=4, top_k=min(self.top_k, 2),
                      num_shared_experts=min(self.num_shared_experts, 1),
                      moe_d_ff=min(self.moe_d_ff or self.d_ff, 256),
                      first_k_dense=min(self.first_k_dense, 1))
        if self.use_mla:
            kw.update(kv_lora_rank=64, qk_nope_head_dim=32,
                      qk_rope_head_dim=16, v_head_dim=32)
        if self.arch_type == "ssm":
            kw.update(ssm_state=32, ssm_head_dim=32, ssm_chunk=16)
        if self.arch_type == "hybrid":
            kw.update(lru_width=256, local_window=32, num_layers=3)
        if self.is_encoder_decoder:
            kw.update(encoder_layers=2, encoder_seq=16)
        if self.attention_window:
            kw.update(attention_window=32)
        return self.replace(**kw)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: Optional[float] = None):
    """Truncated-normal fan-in init (LeCun-ish), matching llama-family."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale if scale is not None else fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def split_keys(key, n):
    return list(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Norms / activations
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-6,
             use_pallas: bool = False) -> jnp.ndarray:
    if use_pallas:
        from repro.kernels import ops as kops
        return kops.rmsnorm(x, weight, eps=eps)
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(dtype)


def layer_norm(x, weight, bias, eps=1e-5):
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    out = out * weight.astype(jnp.float32) + bias.astype(jnp.float32)
    return out.astype(dtype)


def ffn_act(x_gate, x_up, kind: str):
    """Combine gate/up projections per the configured activation."""
    if kind == "swiglu":
        return jax.nn.silu(x_gate) * x_up
    if kind == "squared_relu":            # nemotron-4
        r = jax.nn.relu(x_gate)
        return r * r
    if kind == "gelu":                    # whisper / starcoder-style
        return jax.nn.gelu(x_gate, approximate=True)
    if kind == "geglu":                   # recurrentgemma MLP
        return jax.nn.gelu(x_gate, approximate=True) * x_up
    raise ValueError(f"unknown ffn activation {kind!r}")


def is_gated(kind: str) -> bool:
    return kind in ("swiglu", "geglu")


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)                     # (head_dim//2,)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_frequencies(head_dim, theta)             # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]                # (..., seq, 1, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jnp.ndarray:
    """Whisper-style fixed sinusoidal embeddings (length, dim)."""
    log_timescale = jnp.log(10000.0) / (dim // 2 - 1)
    inv = jnp.exp(-log_timescale * jnp.arange(dim // 2, dtype=jnp.float32))
    t = jnp.arange(length, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.concatenate([jnp.sin(t), jnp.cos(t)], axis=-1)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------

def softmax_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                          mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Mean next-token loss. logits (B,S,V) fp-any, labels (B,S) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        nll = nll * mask
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def remat_wrap(fn, enabled: bool):
    return jax.checkpoint(fn) if enabled else fn


def scan_layers(body, carry, stacked_xs, *, unroll: bool):
    """lax.scan over stacked layer params/caches, or a python unroll when
    ``unroll`` (exact XLA cost accounting — scan bodies are costed once).

    body(carry, x) -> (carry, y); ys are re-stacked on unroll so both paths
    return identical pytrees."""
    if not unroll:
        return jax.lax.scan(body, carry, stacked_xs)
    length = jax.tree.leaves(stacked_xs)[0].shape[0]
    ys = []
    for i in range(length):
        x = jax.tree.map(lambda a: a[i], stacked_xs)
        carry, y = body(carry, x)
        ys.append(y)
    if ys and jax.tree.leaves(ys[0]):
        ys_stacked = jax.tree.map(lambda *zs: jnp.stack(zs), *ys)
    else:
        ys_stacked = ys[0] if ys else None
    return carry, ys_stacked
