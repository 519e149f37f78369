"""Flash attention forward — Pallas TPU kernel.

Tiling: grid (batch*heads, q_blocks, kv_blocks), kv minor (TPU executes
the grid sequentially minor-to-major, so the VMEM scratch accumulators
persist across the kv sweep of each q block). Per grid step the kernel
holds one (block_q, hd) query tile and one (block_k, hd) KV tile in
VMEM and maintains the online-softmax running (m, l, acc) — the same
algorithm as models/attention.chunked_attention, with O(block_q *
block_k) live scores.

MXU alignment: block_q/block_k default 128 and hd is 64..256 for every
assigned arch — all multiples of the 128-lane MXU tiles (64 via lane
packing). Causally-dead kv tiles are skipped with pl.when (the §Perf
block-skipping the pure-jnp path lacks).

Validated on CPU with interpret=True against kernels/ref.attention_ref
(see tests/test_kernels.py); compiled for v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, block_q: int, block_k: int, causal: bool,
            kv_blocks: int):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causally-dead tile: every k position strictly after every q position
    live = (not causal) or (j * block_k <= i * block_q + (block_q - 1))

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # (bq, hd)
        k = k_ref[0].astype(jnp.float32)                 # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        if causal:
            qpos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            kpos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_prev * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(p, v)
        m_ref[...] = m_new

    @pl.when(j == kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q,k,v: (B, S, H, hd) full-H form -> (B, S, H, hd).

    interpret=True runs the kernel body in Python on the CPU (the
    validation mode of the tests).
    """
    Bz, S, H, hd = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    assert S % block_q == 0 and T % block_k == 0, (S, T, block_q, block_k)
    qf = q.transpose(0, 2, 1, 3).reshape(Bz * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(Bz * H, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(Bz * H, T, hd)
    kv_blocks = T // block_k
    grid = (Bz * H, S // block_q, kv_blocks)
    scale = 1.0 / (hd ** 0.5)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_q=block_q,
                          block_k=block_k, causal=causal, kv_blocks=kv_blocks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((Bz * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),    # acc
            pltpu.VMEM((block_q,), jnp.float32),       # m (running max)
            pltpu.VMEM((block_q,), jnp.float32),       # l (running denom)
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(Bz, H, S, hd).transpose(0, 2, 1, 3)
