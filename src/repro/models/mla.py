"""Multi-head latent attention (MLA, DeepSeek-V2 arXiv:2405.04434 §2.1),
with ``q_lora_rank`` null.

Per position:

    q = x W_q                   -> H heads of (q_nope | q_pe)
    [c | k_pe] = x W_kva        -> latent c (kv_lora_rank), one shared k_pe
    c = RMSNorm(c)              (kv_a_layernorm)
    [k_nope | v] = c W_kvb      -> H heads of (k_nope | v)
    q_pe, k_pe rotated (YaRN frequencies where configured)
    out = softmax(s (q_nope.k_nope + q_pe.k_pe)) v W_o

Train and prefill expand k_nope and v per head (and broadcast the shared
k_pe over heads) and run the model's attention (``attention.py``: dense,
or above ``rc.attn_dense_max`` positions causal query blocks over key
chunks). Decode is *absorbed*: the cache holds only c and the rotated
k_pe (kv_lora_rank + qk_rope_head_dim values a position), the query is
folded through W_kvb's key half and the latent output through its value
half, so no per-head key or value is formed over the cache.

The published code de-interleaves the rope dims before rotate-half; with
seed-drawn weights that is a fixed permutation of W_q's and W_kva's rope
columns, so the repo's rotate-half is used here and in the reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.layers import (RunConfig, apply_rope, dense_init, rms_norm,
                                 yarn_freqs, yarn_get_mscale)

NEG_INF = -1e30


def init_mla(key, cfg, dtype):
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * (dn + dr)), dtype),
        "wkv_a": dense_init(ks[1], (d, r + dr), dtype),
        "kv_norm": jnp.zeros((r,), jnp.float32),
        "wkv_b": dense_init(ks[2], (r, H * (dn + dv)), dtype),
        "wo": dense_init(ks[3], (H * dv, d), dtype,
                         scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }


def rope_terms(cfg):
    """(inverse frequencies or None, cos/sin factor) of the rope dims."""
    ys = cfg.rope_scaling
    if ys is None:
        return None, 1.0
    freqs = yarn_freqs(cfg.qk_rope_head_dim, cfg.rope_theta, ys)
    return freqs, (yarn_get_mscale(ys.factor, ys.mscale)
                   / yarn_get_mscale(ys.factor, ys.mscale_all_dim))


def softmax_scale(cfg) -> float:
    """q_head_dim ** -0.5, times YaRN's mscale(factor, mscale_all_dim)^2."""
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    ys = cfg.rope_scaling
    if ys is not None and ys.mscale_all_dim:
        m = yarn_get_mscale(ys.factor, ys.mscale_all_dim)
        s = s * m * m
    return s


def _project(params, x, cfg, positions):
    """Queries and the latent cache entries of x (B, S, D)."""
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = jnp.einsum("bsd,df->bsf", x, params["wq"])
    q = q.reshape(q.shape[:2] + (H, dn + dr))
    kv_a = jnp.einsum("bsd,df->bsf", x, params["wkv_a"])
    c = rms_norm(kv_a[..., :r], params["kv_norm"], cfg.norm_eps)
    freqs, msc = rope_terms(cfg)
    q_pe = apply_rope(q[..., dn:], positions, cfg.rope_theta, freqs, msc)
    k_pe = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta, freqs,
                      msc)[..., 0, :]
    return q[..., :dn], q_pe, c, k_pe


def apply_mla(params, x, cfg, rc: RunConfig, positions, *, cache=None,
              cache_index=None):
    """Returns (out (B,S,D), (c, k_pe)): the latent entries of x's
    positions, for prefill's cache or decode's write-back
    (``write_cache``).

    With ``cache`` = (c_cache (B,T,r), pe_cache (B,T,dr)) holding
    positions < ``cache_index``, x is one new token (decode)."""
    H, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    B, S, _ = x.shape
    scale = softmax_scale(cfg)
    qn, qp, c, k_pe = _project(params, x, cfg, positions)
    wkv_b = params["wkv_b"].reshape(r, H, dn + dv)
    if cache is None:
        kv = jnp.einsum("bsc,chf->bshf", c, wkv_b)
        k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
            k_pe[:, :, None], (B, S, H, k_pe.shape[-1]))], -1)
        q = jnp.concatenate([qn, qp], -1)
        if S > rc.attn_dense_max:
            o = attn_lib.chunked_attention(q, k, kv[..., dn:],
                                           chunk=rc.attn_chunk or 1024,
                                           scale=scale)
        else:
            o = attn_lib.full_attention(q, k, kv[..., dn:], causal=True,
                                        scale=scale)
    else:
        o = _absorbed_decode(qn, qp, c, k_pe, cache, cache_index, wkv_b,
                             dn, scale)
    out = jnp.einsum("bsf,fd->bsd", o.reshape(B, S, H * dv), params["wo"])
    return out, (c, k_pe)


def write_cache(stack, entries, index):
    """A stack's latent cache (L, B, T, .) with the new position's
    entries (L, B, 1, .) written at ``index``."""
    return jax.lax.dynamic_update_slice(
        stack, entries.astype(stack.dtype), (0, 0, index, 0))


def _absorbed_decode(qn, qp, c, k_pe, cache, index, wkv_b, dn, scale):
    """One token (S = 1) against the latent cache, never expanding it."""
    c_cache, pe_cache = cache
    T = c_cache.shape[1]
    q_lat = jnp.einsum("bshn,chn->bshc", qn, wkv_b[..., :dn],
                       preferred_element_type=jnp.float32).astype(c.dtype)
    s_old = (jnp.einsum("bshc,btc->bhst", q_lat, c_cache,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshr,btr->bhst", qp, pe_cache,
                          preferred_element_type=jnp.float32))
    s_new = (jnp.einsum("bshc,bsc->bhs", q_lat, c,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshr,bsr->bhs", qp, k_pe,
                          preferred_element_type=jnp.float32))[..., None]
    s_old = jnp.where(jnp.arange(T) < index, s_old, NEG_INF)
    p = jax.nn.softmax(jnp.concatenate([s_old, s_new], -1) * scale, axis=-1)
    o_lat = (jnp.einsum("bhst,btc->bshc", p[..., :T].astype(c.dtype),
                        c_cache).astype(jnp.float32)
             + jnp.einsum("bhs,bsc->bshc", p[..., T], c.astype(jnp.float32)))
    return jnp.einsum("bshc,chv->bshv", o_lat.astype(c.dtype), wkv_b[..., dn:])
