"""Model assembly: blocks, scan-over-layers, hybrid/cross-attn interleave.

One code path per family:
  dense / moe / audio : homogeneous block stack    -> single lax.scan
  ssm                 : homogeneous Mamba2 stack   -> single lax.scan
  hybrid (zamba2)     : Mamba2 stack in segments, ONE shared attn+MLP
                        block applied after every ``attn_every`` layers
  vlm (llama3.2-V)    : self-attn stack in segments, gated cross-attn
                        layer after every ``cross_attn_every`` layers

Scan-over-layers keeps the HLO O(1) in depth: a 95-layer deepseek-67b
train step lowers to one while-loop body. Params are stored stacked
(leading L axis) so FSDP/TP shardings apply uniformly.

MoE models with leading dense layers (``cfg.first_k_dense``,
DeepSeek-V2) store their layers as stacks by kind, ``blocks = {"dense":
(first_k_dense, ...), "moe": (rest, ...)}``, each scanned in turn; their
cache holds one sub-dict per stack. Latent attention (``cfg.is_mla``) is
chosen inside the block and the cache: it caches only the latent
``c_kv`` and rotated ``k_pe`` of each position.

Every block returns, beside its aux loss, the MoE layer's int32 routing
totals (``moe.STAT_KEYS``; none for a dense block), summed over layers:
``forward`` and ``decode_step`` return them (an empty dict without MoE
layers).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models import mla as mla_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (RunConfig, apply_mlp, embed_init, init_mlp,
                                 rms_norm, softmax_cross_entropy)

# SSM / router leaves that stay f32 through compute-dtype casting
_KEEP_F32 = ("A_log", "dt_bias", "D_skip", "router", "gate")


def _cast_params(params, rc: RunConfig,
                 convert=lambda leaf, dtype: leaf.astype(dtype)):
    """``params`` with every floating leaf but the ``_KEEP_F32`` ones in
    the compute dtype, each cast by ``convert(leaf, dtype)``."""
    def cast(path, leaf):
        name = str(path[-1].key) if path and hasattr(path[-1], "key") else ""
        if any(k in name for k in _KEEP_F32):
            return leaf
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return convert(leaf, rc.cdtype)
        return leaf
    return jax.tree_util.tree_map_with_path(cast, params)


def _astype_in_place(x, dtype):
    """``x.astype(dtype)``, with float32 -> bfloat16 written as integer
    ops: round to nearest, ties to even, and a NaN to the quiet NaN of its
    sign, as ``astype`` rounds.

    The TPU compiler moves a plain narrowing convert of a scanned weight
    slice ahead of the scan's slicing and out of the loop, where it
    writes a bfloat16 copy of the whole stack every call; these integer
    ops stay where they are written."""
    if x.dtype != jnp.float32 or dtype != jnp.bfloat16:
        return x.astype(dtype)
    u = jax.lax.bitcast_convert_type(x, jnp.uint32)
    r = (u + 0x7FFF + ((u >> 16) & 1)) >> 16
    r = jnp.where((u & 0x7FFFFFFF) > 0x7F800000, ((u >> 16) & 0x8000) | 0x7FC0, r)
    return jax.lax.bitcast_convert_type(r.astype(jnp.uint16), jnp.bfloat16)


def _cast_at_use(tree, rc: RunConfig):
    """Decode's cast of what a step is about to use: a layer's slice in
    the scan body, the gathered embedding rows, the head. The weights are
    read once, in their stored dtype, where they are used."""
    return _cast_params(tree, rc, _astype_in_place)


# ---------------------------------------------------------------------------
# Block initialisers
# ---------------------------------------------------------------------------
def _init_attn_block(key, cfg, dtype, use_moe: bool):
    k1, k2 = jax.random.split(key)
    init_attn = mla_lib.init_mla if cfg.is_mla else attn_lib.init_attention
    p = {
        "ln1": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": init_attn(k1, cfg, dtype),
        "ln2": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if use_moe:
        p["moe"] = moe_lib.init_moe(k2, cfg, dtype)
    else:
        p["mlp"] = init_mlp(k2, cfg.d_model, cfg.d_ff, dtype)
    return p


def _init_mamba_block(key, cfg, dtype):
    return {
        "ln": jnp.zeros((cfg.d_model,), jnp.float32),
        "mamba": ssm_lib.init_mamba(key, cfg, dtype),
    }


def _init_cross_block(key, cfg, dtype):
    return {
        "ln": jnp.zeros((cfg.d_model,), jnp.float32),
        "attn": attn_lib.init_attention(key, cfg, dtype, cross=True),
        "gate": jnp.zeros((), jnp.float32),
    }


def init_params(cfg, key, rc: RunConfig) -> Dict[str, Any]:
    dtype = rc.pdtype
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], (cfg.vocab_padded, cfg.d_model), dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["head"] = embed_init(keys[1], (cfg.vocab_padded, cfg.d_model), dtype)

    L = cfg.n_layers
    if cfg.first_k_dense:
        if cfg.family != "moe":
            raise ValueError("first_k_dense needs an MoE model")
        k = cfg.first_k_dense
        params["blocks"] = {
            "dense": jax.vmap(lambda kk: _init_attn_block(
                kk, cfg, dtype, use_moe=False))(jax.random.split(keys[2], k)),
            "moe": jax.vmap(lambda kk: _init_attn_block(
                kk, cfg, dtype, use_moe=True))(jax.random.split(keys[3], L - k))}
    elif cfg.family in ("dense", "audio", "vlm"):
        params["blocks"] = jax.vmap(
            lambda k: _init_attn_block(k, cfg, dtype, use_moe=False)
        )(jax.random.split(keys[2], L))
    elif cfg.family == "moe":
        params["blocks"] = jax.vmap(
            lambda k: _init_attn_block(k, cfg, dtype, use_moe=True)
        )(jax.random.split(keys[2], L))
    elif cfg.family in ("ssm", "hybrid"):
        params["blocks"] = jax.vmap(
            lambda k: _init_mamba_block(k, cfg, dtype)
        )(jax.random.split(keys[2], L))
        if cfg.family == "hybrid":
            params["shared_block"] = _init_attn_block(keys[3], cfg, dtype, use_moe=False)
    else:
        raise ValueError(cfg.family)

    if cfg.family == "vlm":
        n_cross = cfg.n_layers // cfg.cross_attn_every
        params["cross_blocks"] = jax.vmap(
            lambda k: _init_cross_block(k, cfg, dtype)
        )(jax.random.split(keys[4], n_cross))
    return params


def _stacks(params, cfg):
    """[(name, stacked block params, use_moe)] in order: the one stack
    (name None), or the "dense" and "moe" stacks of a model with leading
    dense layers."""
    if cfg.first_k_dense:
        return [(k, params["blocks"][k], k == "moe") for k in ("dense", "moe")]
    return [(None, params["blocks"], cfg.family == "moe")]


def _kv_names(cfg):
    """The cache's entries of an attention layer."""
    return ("c_kv", "k_pe") if cfg.is_mla else ("k", "v")


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------
def _carry_axes(rc: RunConfig):
    # Megatron-SP: the residual stream parks sequence-sharded on 'tp'
    # between blocks (the axis is idle there) — 16x smaller scan stash.
    return ("dp", "tp", None) if rc.seq_shard_carry else ("dp", None, None)


def _enter(x, rc: RunConfig):
    """SP block entry: ONE all-gather of the post-norm activations."""
    if rc.seq_shard_carry:
        return rc.constrain(x, ("dp", None, None))
    return x


def _residual_add(h, delta, rc: RunConfig, block_exit: bool = False):
    """SP: reduce-scatter the block output into the sharded carry.
    Without SP, constrain only at the block exit (mid-block constraints
    measurably regressed the MoE cells — see §Perf cell B notes)."""
    if rc.seq_shard_carry:
        delta = rc.constrain(delta, _carry_axes(rc))
        return rc.constrain(h + delta, _carry_axes(rc))
    if block_exit or rc.attn_exit_constrain:
        return rc.constrain(h + delta, _carry_axes(rc))
    return h + delta


def _apply_attn_block(bp, h, cfg, rc, positions, *, cache=None, cache_index=None,
                      return_kv=False):
    """Returns (h, kv, aux, stats); stats is {} for a dense MLP block."""
    x1 = _enter(rms_norm(h, bp["ln1"], cfg.norm_eps), rc)
    if cfg.is_mla:
        a, kv = mla_lib.apply_mla(bp["attn"], x1, cfg, rc, positions,
                                  cache=cache, cache_index=cache_index)
    else:
        a, kv = attn_lib.apply_attention(
            bp["attn"], x1, cfg, rc, positions,
            cache=cache, cache_index=cache_index, return_kv=return_kv)
    h = _residual_add(h, a, rc)
    aux, stats = jnp.zeros((), jnp.float32), {}
    x2 = _enter(rms_norm(h, bp["ln2"], cfg.norm_eps), rc)
    if "moe" in bp:
        m, aux, stats = moe_lib.apply_moe(bp["moe"], x2, cfg, rc)
    else:
        m = apply_mlp(bp["mlp"], x2, gelu=cfg.gelu_mlp)
    h = _residual_add(h, m, rc, block_exit=True)
    return h, kv, aux, stats


def _add_stats(a, b):
    return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}


def _apply_mamba_block(bp, h, cfg, rc, *, state=None, return_state=False):
    x1 = _enter(rms_norm(h, bp["ln"], cfg.norm_eps), rc)
    y, new_state = ssm_lib.apply_mamba(
        bp["mamba"], x1, cfg, rc, state=state, return_state=return_state)
    return _residual_add(h, y, rc, block_exit=True), new_state


def _apply_cross_block(bp, h, cfg, rc, img_embeds, *, cache=None):
    a, kv = attn_lib.apply_attention(
        bp["attn"], rms_norm(h, bp["ln"], cfg.norm_eps), cfg, rc, None,
        kv_x=img_embeds, causal=False, cache=cache, return_kv=True,
        is_cross=True)
    h = h + jnp.tanh(bp["gate"]).astype(h.dtype) * a
    return h, kv


def _maybe_remat(fn, rc: RunConfig):
    if not rc.remat:
        return fn
    if rc.remat_policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------
def forward(params, cfg, rc: RunConfig, *, tokens=None, embeds=None,
            img_embeds=None, return_cache: bool = False,
            last_only: bool = False):
    """Full-sequence forward.

    Returns (logits, aux_loss, cache, stats) — cache is None unless
    ``return_cache`` (prefill), and is a dict matching init_cache's
    structure with pos = S. ``last_only`` emits logits for the final
    position only (what serving prefill actually needs — skips the
    (B,S,V) logits tensor entirely). ``stats`` holds the MoE routing
    totals (empty without MoE layers).
    """
    params = _cast_params(params, rc)
    if embeds is not None:
        h = embeds.astype(rc.cdtype)
        B, S = h.shape[:2]
    else:
        h = jnp.take(params["embed"], tokens, axis=0)
        B, S = tokens.shape
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model ** 0.5, rc.cdtype)
    h = rc.constrain(h, ("dp", None, None))
    positions = jnp.arange(S)[None, :]

    aux_total = jnp.zeros((), jnp.float32)
    stats: Dict[str, Any] = {}
    cache: Optional[Dict[str, Any]] = {} if return_cache else None

    def body(carry, bp):
        hh, aux, st = carry
        hh, kv, a, s = _apply_attn_block(bp, hh, cfg, rc, positions,
                                         return_kv=return_cache)
        return (hh, aux + a, _add_stats(st, s)), kv
    body = _maybe_remat(body, rc)

    if cfg.family in ("dense", "moe", "audio", "vlm"):
        if cfg.family == "vlm" and img_embeds is not None:
            h, cache, aux_total = _vlm_forward(params, cfg, rc, h, positions,
                                               img_embeds, return_cache)
        else:
            for name, blocks, use_moe in _stacks(params, cfg):
                st0 = moe_lib.zero_stats() if use_moe else {}
                (h, aux_total, st), kvs = jax.lax.scan(
                    body, (h, aux_total, st0), blocks)
                stats = _add_stats(stats, st) if stats else st
                if return_cache:
                    sub = dict(zip(_kv_names(cfg), kvs))
                    if name is None:
                        cache.update(sub)
                    else:
                        cache[name] = sub
    elif cfg.family == "ssm":
        def body(carry, bp):
            hh, aux = carry
            hh, st = _apply_mamba_block(bp, hh, cfg, rc, return_state=return_cache)
            return (hh, aux), st
        body = _maybe_remat(body, rc)
        (h, aux_total), states = jax.lax.scan(body, (h, aux_total), params["blocks"])
        if return_cache:
            cache = {"ssm": states}
    elif cfg.family == "hybrid":
        h, cache, aux_total = _hybrid_forward(params, cfg, rc, h, positions,
                                              return_cache)
    else:
        raise ValueError(cfg.family)

    if last_only:
        h = h[:, -1:, :]
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,vd->bsv", h, head)
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    logits = rc.constrain(logits, ("dp", None, "tp"))
    if return_cache and cache is not None:
        cache["pos"] = jnp.asarray(S, jnp.int32)
    return logits, aux_total, cache, stats


def _segments(n_layers: int, every: int):
    """[(a, b, apply_special_after), ...] covering n_layers in chunks."""
    segs = []
    a = 0
    while a < n_layers:
        b = min(a + every, n_layers)
        segs.append((a, b, b - a == every))
        a = b
    return segs


def _slice_stack(tree, a: int, b: int):
    return jax.tree.map(lambda p: p[a:b], tree)


def _hybrid_forward(params, cfg, rc, h, positions, return_cache):
    aux = jnp.zeros((), jnp.float32)
    cache = {"ssm": [], "k": [], "v": []} if return_cache else None

    def body(carry, bp):
        hh = carry
        hh, st = _apply_mamba_block(bp, hh, cfg, rc, return_state=return_cache)
        return hh, st
    body = _maybe_remat(body, rc)

    for a, b, full in _segments(cfg.n_layers, cfg.attn_every):
        h, states = jax.lax.scan(body, h, _slice_stack(params["blocks"], a, b))
        if return_cache:
            cache["ssm"].append(states)
        if full:
            h, kv, a_, _ = _apply_attn_block(params["shared_block"], h, cfg, rc,
                                             positions, return_kv=return_cache)
            aux = aux + a_
            if return_cache:
                cache["k"].append(kv[0])
                cache["v"].append(kv[1])
    if return_cache:
        cache["ssm"] = jax.tree.map(lambda *xs: jnp.concatenate(xs, 0), *cache["ssm"]) \
            if len(cache["ssm"]) > 1 else cache["ssm"][0]
        cache["k"] = jnp.stack(cache["k"], 0)
        cache["v"] = jnp.stack(cache["v"], 0)
    return h, cache, aux


def _vlm_forward(params, cfg, rc, h, positions, img_embeds, return_cache):
    aux = jnp.zeros((), jnp.float32)
    cache = {"k": [], "v": [], "xk": [], "xv": []} if return_cache else None
    img = img_embeds.astype(rc.cdtype)

    def body(carry, bp):
        hh = carry
        hh, kv, _, _ = _apply_attn_block(bp, hh, cfg, rc, positions,
                                         return_kv=return_cache)
        return hh, kv
    body = _maybe_remat(body, rc)

    n_cross = cfg.n_layers // cfg.cross_attn_every
    ci = 0
    for a, b, full in _segments(cfg.n_layers, cfg.cross_attn_every):
        h, kvs = jax.lax.scan(body, h, _slice_stack(params["blocks"], a, b))
        if return_cache:
            cache["k"].append(kvs[0])
            cache["v"].append(kvs[1])
        if full and ci < n_cross:
            cb = _slice_stack(params["cross_blocks"], ci, ci + 1)
            cb = jax.tree.map(lambda p: p[0], cb)
            h, xkv = _apply_cross_block(cb, h, cfg, rc, img)
            if return_cache:
                cache["xk"].append(xkv[0])
                cache["xv"].append(xkv[1])
            ci += 1
    if return_cache:
        cache["k"] = jnp.concatenate(cache["k"], 0)
        cache["v"] = jnp.concatenate(cache["v"], 0)
        cache["xk"] = jnp.stack(cache["xk"], 0)
        cache["xv"] = jnp.stack(cache["xv"], 0)
    return h, cache, aux


# ---------------------------------------------------------------------------
# Decode (single token against a cache)
# ---------------------------------------------------------------------------
def init_cache(cfg, rc: RunConfig, batch: int, max_len: int):
    """Zeroed decode cache. Matches the structure forward(return_cache=True)
    produces (modulo max_len sizing)."""
    K, hd, L = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_layers
    cdt = rc.cdtype

    def attn_cache(n):
        if cfg.is_mla:       # latent entries only
            return {"c_kv": jnp.zeros((n, batch, max_len, cfg.kv_lora_rank),
                                      cdt),
                    "k_pe": jnp.zeros((n, batch, max_len,
                                       cfg.qk_rope_head_dim), cdt)}
        return {"k": jnp.zeros((n, batch, max_len, K, hd), cdt),
                "v": jnp.zeros((n, batch, max_len, K, hd), cdt)}

    if cfg.family in ("dense", "moe", "audio"):
        c = ({"dense": attn_cache(cfg.first_k_dense),
              "moe": attn_cache(L - cfg.first_k_dense)}
             if cfg.first_k_dense else attn_cache(L))
    elif cfg.family == "vlm":
        n_cross = L // cfg.cross_attn_every
        c = {"k": jnp.zeros((L, batch, max_len, K, hd), cdt),
             "v": jnp.zeros((L, batch, max_len, K, hd), cdt),
             "xk": jnp.zeros((n_cross, batch, cfg.n_img_tokens, K, hd), cdt),
             "xv": jnp.zeros((n_cross, batch, cfg.n_img_tokens, K, hd), cdt)}
    elif cfg.family == "ssm":
        st = ssm_lib.init_ssm_state(cfg, batch, cdt)
        c = {"ssm": jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (L,) + x.shape), st)}
    elif cfg.family == "hybrid":
        st = ssm_lib.init_ssm_state(cfg, batch, cdt)
        n_apps = sum(1 for *_, f in _segments(L, cfg.attn_every) if f)
        c = {"ssm": jax.tree.map(
                 lambda x: jnp.broadcast_to(x[None], (L,) + x.shape), st),
             "k": jnp.zeros((n_apps, batch, max_len, K, hd), cdt),
             "v": jnp.zeros((n_apps, batch, max_len, K, hd), cdt)}
    else:
        raise ValueError(cfg.family)
    c["pos"] = jnp.zeros((), jnp.int32)
    return c


def decode_step(params, cfg, rc: RunConfig, cache, tokens, *, embeds=None):
    """One decode step. tokens: (B, 1) int32 (or embeds (B,1,D) for audio).

    Returns (logits (B,1,Vp), new_cache, stats): stats are the MoE
    routing totals (empty without MoE layers). Each weight is cast to the
    compute dtype where it is used (``_cast_at_use``)."""
    index = cache["pos"]
    if embeds is not None:
        h = embeds.astype(rc.cdtype)
    else:
        h = _cast_at_use(jnp.take(params["embed"], tokens, axis=0), rc)
    if cfg.scale_embeddings:
        h = h * jnp.asarray(cfg.d_model ** 0.5, rc.cdtype)
    positions = jnp.broadcast_to(index[None, None], tokens.shape[:1] + (1,)) \
        if tokens is not None else jnp.full((h.shape[0], 1), index)

    new_cache = dict(cache)
    stats: Dict[str, Any] = {}
    if cfg.family in ("dense", "moe", "audio"):
        names = _kv_names(cfg)

        def body(carry, xs):
            hh, st = carry
            bp, *kv = xs
            hh, kv, _, s = _apply_attn_block(_cast_at_use(bp, rc), hh, cfg,
                                             rc, positions,
                                             cache=tuple(kv), cache_index=index)
            return (hh, _add_stats(st, s)), kv
        for name, blocks, use_moe in _stacks(params, cfg):
            sub = cache if name is None else cache[name]
            st0 = moe_lib.zero_stats() if use_moe else {}
            (h, st), kvs = jax.lax.scan(
                body, (h, st0), (blocks,) + tuple(sub[n] for n in names))
            stats = _add_stats(stats, st) if stats else st
            if cfg.is_mla:
                # the new position's latent entries, written in place
                sub = {n: mla_lib.write_cache(sub[n], e, index)
                       for n, e in zip(names, kvs)}
            else:
                sub = dict(zip(names, kvs))
            if name is None:
                new_cache.update(sub)
            else:
                new_cache[name] = sub
    elif cfg.family == "vlm":
        h, new_cache = _vlm_decode(params, cfg, rc, h, positions, cache, index)
    elif cfg.family == "ssm":
        def body(hh, xs):
            bp, st = xs
            hh, st2 = _apply_mamba_block(_cast_at_use(bp, rc), hh, cfg, rc,
                                         state=ssm_lib.SSMState(*st))
            return hh, tuple(st2)
        h, states = jax.lax.scan(body, h, (params["blocks"], tuple(cache["ssm"])))
        new_cache["ssm"] = ssm_lib.SSMState(*states)
    elif cfg.family == "hybrid":
        h, new_cache = _hybrid_decode(params, cfg, rc, h, positions, cache, index)
    else:
        raise ValueError(cfg.family)

    h = rms_norm(h, _cast_at_use(params["final_norm"], rc), cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["head"]
    logits = jnp.einsum("bsd,vd->bsv", h, _cast_at_use(head, rc))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    new_cache["pos"] = index + 1
    return logits, new_cache, stats


def _hybrid_decode(params, cfg, rc, h, positions, cache, index):
    new_cache = dict(cache)
    ssm_states = []
    ks, vs = [], []
    app = 0

    def body(hh, xs):
        bp, st = xs
        hh, st2 = _apply_mamba_block(_cast_at_use(bp, rc), hh, cfg, rc,
                                     state=ssm_lib.SSMState(*st))
        return hh, tuple(st2)

    for a, b, full in _segments(cfg.n_layers, cfg.attn_every):
        seg_state = jax.tree.map(lambda p: p[a:b], tuple(cache["ssm"]))
        h, states = jax.lax.scan(body, h, (_slice_stack(params["blocks"], a, b), seg_state))
        ssm_states.append(states)
        if full:
            h, kv, _, _ = _apply_attn_block(
                _cast_at_use(params["shared_block"], rc), h, cfg, rc, positions,
                cache=(cache["k"][app], cache["v"][app]), cache_index=index)
            ks.append(kv[0])
            vs.append(kv[1])
            app += 1
    new_cache["ssm"] = ssm_lib.SSMState(*jax.tree.map(
        lambda *xs: jnp.concatenate(xs, 0), *ssm_states))
    new_cache["k"] = jnp.stack(ks, 0)
    new_cache["v"] = jnp.stack(vs, 0)
    return h, new_cache


def _vlm_decode(params, cfg, rc, h, positions, cache, index):
    new_cache = dict(cache)
    ks, vs = [], []
    ci = 0
    n_cross = cfg.n_layers // cfg.cross_attn_every

    def body(hh, xs):
        bp, kc, vc = xs
        hh, kv, _, _ = _apply_attn_block(_cast_at_use(bp, rc), hh, cfg, rc,
                                         positions, cache=(kc, vc),
                                         cache_index=index)
        return hh, kv

    for a, b, full in _segments(cfg.n_layers, cfg.cross_attn_every):
        h, kvs = jax.lax.scan(
            body, h, (_slice_stack(params["blocks"], a, b),
                      cache["k"][a:b], cache["v"][a:b]))
        ks.append(kvs[0])
        vs.append(kvs[1])
        if full and ci < n_cross:
            cb = _cast_at_use(jax.tree.map(lambda p: p[ci],
                                           params["cross_blocks"]), rc)
            h, _ = _apply_cross_block(cb, h, cfg, rc, None,
                                      cache=(cache["xk"][ci], cache["xv"][ci]))
            ci += 1
    new_cache["k"] = jnp.concatenate(ks, 0)
    new_cache["v"] = jnp.concatenate(vs, 0)
    return h, new_cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(logits, labels, cfg, aux=None, aux_weight: float = 0.01):
    ce = softmax_cross_entropy(logits, labels, cfg.vocab_size).mean()
    if aux is not None:
        ce = ce + aux_weight * aux
    return ce
