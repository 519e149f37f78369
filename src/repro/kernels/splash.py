"""Causal self-attention on the TPU through the splash-attention Pallas
kernel that ships with jax (``jax.experimental.pallas.ops.tpu.
splash_attention``): a forward kernel and dq / dkv backward kernels
behind a ``custom_vjp``, every score tile kept in VMEM, key blocks past
the diagonal never visited. It takes q/k and v head dims that differ
(MLA's 192 / 128).

Layout: full-H (B,S,H,d) in and out, as ``models/attention.py``; the
kernel itself runs head-major (H,S,d), mapped over the batch. It takes
no softmax scale, so the scale is folded into q (multiplied in f32,
cast back once). Scores and the softmax are f32, p meets v in the
operands' dtype: what ``chunked_attention`` computes.

Block sizes follow from S alone (``block_sizes``); the kernel and its
mask tables are built once per (S, heads) in a process.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as sk, splash_attention_mask as sm)

LANES = 128


def supports(S: int, T: int) -> bool:
    """Whether the kernel takes causal self-attention of S queries over T
    keys: S == T, both whole numbers of 128-row blocks."""
    return S == T and S % LANES == 0


def block_sizes(S: int) -> sk.BlockSizes:
    """The forward's and both backward kernels' tiles for S positions:
    the largest of 512, 256, 128 rows that divides S."""
    b = next(b for b in (512, 256, LANES) if S % b == 0)
    return sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                         block_q_dkv=b, block_kv_dkv=b,
                         block_kv_dkv_compute=b, block_q_dq=b,
                         block_kv_dq=b)


@functools.lru_cache(maxsize=None)
def _kernel(S: int, H: int, interpret: bool):
    mask = sm.MultiHeadMask([sm.CausalMask((S, S))] * H)
    # the mask tables become arrays: made here, not inside a caller's trace
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(mask, block_sizes=block_sizes(S),
                                  head_shards=1, q_seq_shards=1,
                                  interpret=interpret)


def causal_attention(q, k, v, *, scale: float, interpret: bool = False):
    """Causal softmax(scale q.k) v for q, k (B,S,H,dq), v (B,S,H,dv),
    S a multiple of 128 (``supports``)."""
    B, S, H, _ = q.shape
    assert supports(S, k.shape[1]), (q.shape, k.shape)
    kernel = _kernel(S, H, interpret)
    q = (q.astype(jnp.float32) * scale).astype(k.dtype)
    out = jax.vmap(kernel)(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)))
    return out.transpose(0, 2, 1, 3)
