"""Shared neural-net layers (pure functions over param pytrees)."""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# Run-time configuration threaded through model code.
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class RunConfig:
    """How to *run* a model (orthogonal to ArchConfig = what the model is)."""

    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    remat: bool = False                # activation checkpointing over blocks
    remat_policy: str = "none"        # dots = save matmul outputs;
                                       # anything else = full remat
    attn_chunk: int = 0                # >0: online-softmax chunked attention block
    attn_dense_max: int = 8192         # use dense attention up to this seq_len
    attn_shard: str = "heads"          # 'heads' | 'seq' (q-sequence TP when
                                       #  n_heads doesn't divide the TP axis)
    attn_exit_constrain: bool = False  # constrain h after the attention
                                       # residual too (helps llama4-MoE,
                                       # hurts qwen2-moe — per-arch knob)
    seq_shard_carry: bool = False      # Megatron-SP: shard the residual
                                       # stream (B,S,D) over 'tp' between
                                       # blocks — 16x smaller layer-scan
                                       # stash at the cost of AG/RS pairs
    moe_group: int = 2048              # MoE dispatch group size (tokens)
    ssd_chunk: int = 0                 # SSD chunk override (0 = ArchConfig's)
    # logical-axis -> PartitionSpec constrain hook, injected by the runtime.
    # Signature: constrain(x, logical_axes: tuple) -> x.  Default: identity.
    constrain: Callable = field(default=lambda x, axes: x)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    def replace(self, **kw) -> "RunConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape, dtype, scale: float = 1.0):
    """Truncated-normal fan-in initializer (what most LMs ship with)."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / jnp.sqrt(jnp.asarray(fan_in, jnp.float32))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-5):
    """RMSNorm in f32, cast back to input dtype; scale is (1 + g)."""
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def linear(x, w, b=None):
    y = jnp.einsum("...d,df->...f", x, w)
    if b is not None:
        y = y + b
    return y


def swiglu(x, w1, w3, w2):
    """SwiGLU MLP: w2( silu(x w1) * (x w3) )."""
    return linear(jax.nn.silu(linear(x, w1)) * linear(x, w3), w2)


def geglu(x, w1, w3, w2):
    """GeGLU MLP (gemma): w2( gelu(x w1) * (x w3) )."""
    return linear(jax.nn.gelu(linear(x, w1), approximate=True) * linear(x, w3), w2)


def init_mlp(key, d_model: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": dense_init(k1, (d_model, d_ff), dtype),
        "w3": dense_init(k2, (d_model, d_ff), dtype),
        "w2": dense_init(k3, (d_ff, d_model), dtype),
    }


def apply_mlp(params, x, gelu: bool = False):
    fn = geglu if gelu else swiglu
    return fn(x, params["w1"], params["w3"], params["w2"])


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)          # (head_dim//2,)


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor (DeepSeek-V2's yarn_get_mscale)."""
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim: int, theta: float, ys) -> jax.Array:
    """YaRN inverse frequencies (``ys`` a configs.base.YarnScaling): each
    frequency is ramped from its extrapolated value (rotation counts
    above ``beta_fast`` in the original context) to its value
    interpolated by ``factor`` (counts below ``beta_slow``)."""
    def corr_dim(rotations):
        return (head_dim * math.log(ys.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(ys.beta_fast)), 0)
    high = min(math.ceil(corr_dim(ys.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(head_dim, theta)
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                        # 1 = extrapolate
    return extra / ys.factor * (1.0 - keep) + extra * keep


def apply_rope(x, positions, theta: float, freqs=None, mscale: float = 1.0):
    """x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S).
    ``freqs`` replaces the plain inverse frequencies (YaRN), ``mscale``
    scales cos and sin."""
    head_dim = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(head_dim, theta)                   # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Losses
# ---------------------------------------------------------------------------
def softmax_cross_entropy(logits, labels, vocab_size: int):
    """CE in f32 with padded-vocab masking. logits: (..., Vp), labels ints."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp > vocab_size:  # mask padded vocab slots out of the softmax
        pad_mask = (jnp.arange(vp) >= vocab_size)
        logits = jnp.where(pad_mask, -1e9, logits)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - gold
