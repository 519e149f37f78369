"""Jit'd dispatch wrappers for the Pallas kernels.

Backends (callers name one; nothing is picked behind their back):
  'pallas'     — native TPU lowering (production target)
  'interpret'  — Pallas interpret mode (kernel body on CPU; validation)
  'jnp'        — the pure-jnp production paths (models/attention,
                 models/ssm), used by the distributed dry-run
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssd_mod
from repro.models import attention as attn_lib
from repro.models import ssm as ssm_lib


def attention(q, k, v, *, impl: str, causal: bool = True,
              block_q: int = 128, block_k: int = 128, chunk: int = 1024):
    """Full-H attention (B,S,H,hd)x3 -> (B,S,H,hd)."""
    if impl in ("pallas", "interpret"):
        return fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                  block_k=block_k,
                                  interpret=impl == "interpret")
    if impl == "jnp":
        if q.shape[1] > chunk:
            return attn_lib.chunked_attention(q, k, v, chunk=chunk,
                                              causal=causal)
        return attn_lib.full_attention(q, k, v, causal=causal)
    raise ValueError(impl)


def ssd(x, dt, A, B, C, *, impl: str, chunk: int = 128):
    """Chunked SSD scan -> (y, final_state)."""
    if impl in ("pallas", "interpret"):
        return ssd_mod.ssd_scan(x, dt, A, B, C, chunk=chunk,
                                interpret=impl == "interpret")
    if impl == "jnp":
        dtf = jnp.asarray(dt, jnp.float32)
        return ssm_lib.ssd_chunked(x, dtf, A, B, C, chunk)
    raise ValueError(impl)
