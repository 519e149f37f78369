"""Attention: GQA, dense + chunked(online-softmax) + decode-with-cache paths.

Shapes convention:
  q: (B, S, H, hd)    k/v: (B, T, K, hd)    H = K * G   (GQA groups)

Sharding design (see DESIGN.md §5): prefill/train attention computes in
full-H form — KV heads are broadcast to H *after* projection (GQA saves
KV memory/bandwidth, not score FLOPs) and scores are sharded over the
head axis ('tp'). This keeps every contraction (head_dim, seq) unsharded
so the only model-parallel collective per block is the Megatron
row-parallel all-reduce at wo/w2. Decode keeps the (K, G) folded form:
the KV cache stays in K heads (the big tensor) and the tiny score psum
is cheaper than materializing a repeated cache.

The chunked path is the memory-subquadratic attention used for long
prefill and training (32k prefill, DeepSeek-V2's 8k train step):
causal query blocks against the key chunks up to their own, one block's
O(chunk^2) scores live at a time instead of O(S^2). The Pallas flash
kernel (kernels/flash_attention.py) implements the same algorithm for
TPU; ``kernels/ops.py`` dispatches between them.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import RunConfig, apply_rope, dense_init

NEG_INF = -1e30


def init_attention(key, cfg, dtype, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, K = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, K * hd), dtype),
        "wv": dense_init(ks[2], (d, K * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def repeat_kv(k, n_heads: int):
    """(B,T,K,hd) -> (B,T,H,hd) by broadcasting each KV head over its group."""
    B, T, K, hd = k.shape
    G = n_heads // K
    k = jnp.broadcast_to(k[:, :, :, None, :], (B, T, K, G, hd))
    return k.reshape(B, T, K * G, hd)


def full_attention(q, k, v, *, causal: bool, q_offset: int = 0, scale=None):
    """Dense attention in full-H form. q:(B,S,H,dq) k:(B,T,H,dq)
    v:(B,T,H,dv); ``scale`` defaults to dq ** -0.5."""
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        S, T = scores.shape[-2], scores.shape[-1]
        qpos = jnp.arange(S) + q_offset
        mask = qpos[:, None] >= jnp.arange(T)[None, :]
        scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhst,bthd->bshd", p, v)


def _key_chunk(carry, xs, *, q, scale, q_pos, causal):
    """One chunk of keys into the online softmax of the head-major query
    rows q (B,H,s,dq) at positions ``q_pos``: carry (max, sum, acc)."""
    m, l, acc = carry
    kj, vj, k_pos = xs
    s = jnp.einsum("bhsd,bhtd->bhst", q, kj,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s = jnp.where(q_pos[:, None] >= k_pos[None, :], s, NEG_INF)
    m_new = jnp.maximum(m, s.max(-1))
    corr = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    acc = acc * corr[..., None] + jnp.einsum(
        "bhst,bhtd->bhsd", p.astype(vj.dtype), vj,
        preferred_element_type=jnp.float32)
    return (m_new, l * corr + p.sum(-1), acc), None


def _query_block(q, kc, vc, k_pos, *, scale, q_pos, causal):
    """Head-major rows q (B,H,s,dq) against the key chunks kc, vc
    (n, B,H,chunk, .) at positions k_pos (n, chunk), online."""
    B, H, S = q.shape[:3]
    init = (jnp.full((B, H, S), NEG_INF, jnp.float32),
            jnp.zeros((B, H, S), jnp.float32),
            jnp.zeros((B, H, S, vc.shape[-1]), jnp.float32))
    # each chunk is rematerialised: the backward pass keeps only the
    # running max, sum and output of every chunk, never its scores
    (_, l, acc), _ = jax.lax.scan(
        jax.checkpoint(partial(_key_chunk, q=q, scale=scale, q_pos=q_pos,
                               causal=causal)), init, (kc, vc, k_pos))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).astype(vc.dtype)


def chunked_attention(q, k, v, *, chunk: int, causal: bool = True,
                      scale=None):
    """Online-softmax attention over key chunks of ``chunk`` keys, in
    full-H form: q (B,S,H,dq), k (B,T,H,dq), v (B,T,H,dv); ``scale``
    defaults to dq ** -0.5.

    Causal with S == T a multiple of ``chunk``: query blocks of
    ``chunk`` rows, block i scanning key chunks 0 .. i only (keys past
    the block are never scored, so the FLOPs are about half of S^2),
    one block after the other and each rematerialised, so one block's
    scores live at a time: O(chunk^2) of them. Otherwise every query row
    scans every chunk (masked if causal), O(S * chunk) scores live. No
    softmax row spans more than one chunk (on the TPU a softmax's row
    reductions over many thousand keys compile to a cost quadratic in
    the row)."""
    B, S, H, _ = q.shape
    T = k.shape[1]
    n = T // chunk
    assert n * chunk == T, (T, chunk)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    # head-major: each chunk's products batch over heads
    q = q.transpose(0, 2, 1, 3)
    kc = k.reshape(B, n, chunk, H, -1).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(B, n, chunk, H, -1).transpose(1, 0, 3, 2, 4)
    k_pos = jnp.arange(T).reshape(n, chunk)
    if not causal or S != T:
        out = _query_block(q, kc, vc, k_pos, scale=scale,
                           q_pos=jnp.arange(S), causal=causal)
        return out.transpose(0, 2, 1, 3)
    outs = []
    for i in range(n):
        q_i = q[:, :, i * chunk:(i + 1) * chunk]
        if outs:
            # block i starts once block i - 1 is done, and in the backward
            # pass block i - 1 once block i is (its key and value
            # gradients added on as each block finishes)
            outs[-1], q_i, kc, vc = jax.lax.optimization_barrier(
                (outs[-1], q_i, kc, vc))
        fn = jax.checkpoint(partial(
            _query_block, scale=scale,
            q_pos=i * chunk + jnp.arange(chunk), causal=True))
        outs.append(fn(q_i, kc[:i + 1], vc[:i + 1], k_pos[:i + 1]))
    return jnp.concatenate(outs, axis=2).transpose(0, 2, 1, 3)


def _gqa_fold(q, n_kv):
    """(B,S,H,hd) -> (B,S,K,G,hd)."""
    B, S, H, hd = q.shape
    return q.reshape(B, S, n_kv, H // n_kv, hd)


def decode_attention(q, k_cache, v_cache, index):
    """Single-token decode, GQA-folded. q:(B,1,K,G,hd) caches:(B,T,K,hd)."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    # mixed-precision dot (bf16 x bf16 -> f32): avoids materializing an
    # f32 copy of the whole KV cache (7.5 GB/dev on gemma decode_32k)
    scores = jnp.einsum("bskgh,btkh->bkgst", q, k_cache,
                        preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(k_cache.shape[1]) <= index   # positions written so far
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    return jnp.einsum("bkgst,btkh->bskgh", p, v_cache)


def apply_attention(
    params,
    x,
    cfg,
    rc: RunConfig,
    positions,
    *,
    kv_x=None,                 # cross-attention source (B, N, D); None = self
    causal: bool = True,
    cache: Optional[Tuple] = None,   # (k_cache, v_cache) for decode
    cache_index=None,
    return_kv: bool = False,
    is_cross: bool = False,
):
    """Returns (out, new_kv) where new_kv is (k,v) for caching or None."""
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    cross = is_cross or (kv_x is not None)
    src = kv_x if cross else x

    q = jnp.einsum("bsd,df->bsf", x, params["wq"])
    if "bq" in params:
        q = q + params["bq"]
    q = _split_heads(q, H, hd)

    if cross and cache is not None:
        # cross-attn KV was computed at prefill and lives in the cache
        k, v = cache
    else:
        k = jnp.einsum("bsd,df->bsf", src, params["wk"])
        v = jnp.einsum("bsd,df->bsf", src, params["wv"])
        if "bk" in params:
            k, v = k + params["bk"], v + params["bv"]
        k = _split_heads(k, K, hd)
        v = _split_heads(v, K, hd)
        if not cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    new_kv = None
    if cache is not None and not cross:
        # ---- decode: GQA-folded against the K-head cache ----
        k_cache, v_cache = cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k.astype(k_cache.dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v.astype(v_cache.dtype), cache_index, axis=1)
        new_kv = (k_cache, v_cache)
        out = decode_attention(_gqa_fold(q, K), k_cache, v_cache, cache_index)
        out = out.reshape(out.shape[:2] + (H * hd,))
    else:
        if cache is not None and cross:
            new_kv = (k, v)
        elif return_kv:
            new_kv = (k, v)
        # ---- full-H sharded compute ----
        # 'heads': classic Megatron head-TP (needs H % tp == 0).
        # 'seq':   query-sequence TP — each rank owns a q-row block against
        #          the full KV (always divisible; picked by the runtime when
        #          H doesn't divide the TP axis, e.g. 14 heads on tp=16).
        if rc.attn_shard == "seq":
            q_axes, kv_axes = ("dp", "tp", None, None), ("dp", None, None, None)
        else:
            q_axes = kv_axes = ("dp", None, "tp", None)
        q = rc.constrain(q, q_axes)
        kf = rc.constrain(repeat_kv(k, H), kv_axes)
        vf = rc.constrain(repeat_kv(v, H), kv_axes)
        S = x.shape[1]
        if causal and S > rc.attn_dense_max:
            out = chunked_attention(q, kf, vf, chunk=rc.attn_chunk or 1024,
                                    causal=True)
        else:
            out = full_attention(q, kf, vf, causal=causal)
        out = rc.constrain(out, q_axes)
        out = out.reshape(out.shape[:2] + (H * hd,))

    return jnp.einsum("bsf,fd->bsd", out, params["wo"]), new_kv
