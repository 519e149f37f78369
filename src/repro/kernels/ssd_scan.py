"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid (batch, head_blocks, n_chunks) with the chunk axis minor: the
inter-chunk SSM state of one head block lives in VMEM scratch and
persists across the sequential chunk sweep (the TPU grid runs in
order). Per chunk the kernel computes, entirely in VMEM:

  * the intra-chunk quadratic term (the "attention-like" dual form),
  * the inter-chunk contribution from the carried state,
  * the chunk-boundary state update.

The tiles are laid out head-major — x as (hb, c, P), dt and the
log-decay as (hb, c) — so that every contraction is a 2-D matmul or a
matmul with one leading batch dimension, the only forms Mosaic lowers.
The within-chunk cumulative sum is a matmul with an upper-triangular
ones matrix (Mosaic has no cumsum); its sublane-oriented copy, needed
for the (i, j) decay grid, is a masked lane reduction. The carried state
is kept transposed, (hb, N, P), so the state update contracts over the
chunk axis as a plain batched matmul.

VMEM @ chunk=128, hb=8, P=64, N=128 (mamba2-2.7b, H=80 -> 10 head
blocks): the largest live temporaries are the (hb, c, c) f32 decay and
weight grids at 512 KB each; the x/y tiles and the state are 256 KB
each — well inside v5e's 16 MB default scoped VMEM.

Validated in interpret mode against kernels/ref.ssd_ref and
models/ssm.ssd_chunked (tests/test_kernels.py); compiled for v5e in
tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _kernel(x_ref, dt_ref, dA_ref, B_ref, C_ref, y_ref, st_out_ref, state_ref,
            *, chunk: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0].astype(jnp.float32)          # (hb, c, P)
    dt = dt_ref[0].astype(jnp.float32)        # (hb, c)
    dA = dA_ref[0].astype(jnp.float32)        # (hb, c) log-decay dt*A
    Bm = B_ref[0].astype(jnp.float32)         # (c, N)
    Cm = C_ref[0].astype(jnp.float32)         # (c, N)
    hb = x.shape[0]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = ii >= jj                                               # (c, c) i>=j
    # cum[h, j] = sum_{k<=j} dA[h, k]: row form by an upper-triangular
    # matmul, column form (j on sublanes) by a masked lane reduction
    upper = (ii <= jj).astype(jnp.float32)
    cum = jnp.dot(dA, upper, precision=_HI,
                  preferred_element_type=jnp.float32)            # (hb, c)
    cum_col = jnp.sum(jnp.where(tri[None], dA[:, None, :], 0.0),
                      axis=-1, keepdims=True)                    # (hb, c, 1)

    # ---- intra-chunk (dual / attention-like form) ----------------------
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             precision=_HI)                      # (c, c)
    decay = jnp.exp(jnp.where(tri[None], cum_col - cum[:, None, :], -jnp.inf))
    w = CB[None] * decay * dt[:, None, :]                        # (hb, c, c)
    y_diag = jnp.einsum("hij,hjp->hip", w, x, precision=_HI)

    # ---- inter-chunk contribution from carried state --------------------
    state = state_ref[...]                                       # (hb, N, P)
    Cb = jnp.broadcast_to(Cm[None], (hb,) + Cm.shape)            # (hb, c, N)
    y_off = jnp.exp(cum_col) * jnp.einsum("hin,hnp->hip", Cb, state,
                                          precision=_HI)

    y_ref[0] = (y_diag + y_off).astype(y_ref.dtype)

    # ---- state update ----------------------------------------------------
    seg = jnp.exp(cum[:, -1:] - cum) * dt                        # (hb, c)
    BsT = Bm.T[None] * seg[:, None, :]                           # (hb, N, c)
    st_chunk = jnp.einsum("hnj,hjp->hnp", BsT, x, precision=_HI)
    chunk_decay = jnp.exp(cum_col[:, -1:, :])                    # (hb, 1, 1)
    new_state = chunk_decay * state + st_chunk
    state_ref[...] = new_state
    st_out_ref[0] = new_state                 # last write = final state


def _head_block(h: int) -> int:
    return 8 if h % 8 == 0 else h


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD. x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,n).

    Returns (y:(b,s,h,p), final_state:(b,h,p,n)). interpret=True runs
    the kernel body on the CPU (the validation mode of the tests).
    """
    b, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    hb = _head_block(h)
    grid = (b, h // hb, s // chunk)

    xh = x.transpose(0, 2, 1, 3)                                 # (b, h, s, p)
    dth = dt.astype(jnp.float32).transpose(0, 2, 1)              # (b, h, s)
    dAh = dth * A.astype(jnp.float32)[None, :, None]

    y, st = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda i, g, c: (i, g, c, 0)),
            pl.BlockSpec((1, hb, chunk), lambda i, g, c: (i, g, c)),
            pl.BlockSpec((1, hb, chunk), lambda i, g, c: (i, g, c)),
            pl.BlockSpec((1, chunk, n), lambda i, g, c: (i, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, g, c: (i, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, p), lambda i, g, c: (i, g, c, 0)),
            pl.BlockSpec((1, hb, n, p), lambda i, g, c: (i, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, n, p), jnp.float32)],
        interpret=interpret,
    )(xh, dth, dAh, B, C)
    return y.transpose(0, 2, 1, 3), st.transpose(0, 1, 3, 2)
