"""Serving steps: prefill (sequence -> cache) and decode (token + cache).

Both are built with explicit shardings so the decode cells of the
dry-run (`decode_32k`, `long_500k`) lower exactly what production would
run: one new token against a seq_len-deep cache.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import Model, RunConfig, build
from repro.parallel.mesh import make_constrain, pick_attn_shard
from repro.parallel.sharding import (ShardingPolicy, batch_specs, cache_specs,
                                     param_specs, to_named)
from repro.runtime.specs import decode_batch_specs, prefill_batch_specs


def build_prefill_step(cfg, mesh: Optional[Mesh], *, B: int, S: int,
                       rc: Optional[RunConfig] = None,
                       policy: Optional[ShardingPolicy] = None):
    """Returns (jitted, params_sds, batch_sds, param_sh, model)."""
    policy = policy or ShardingPolicy()
    rc = rc or RunConfig()
    if mesh is not None:
        rc = rc.replace(constrain=make_constrain(mesh, policy.r()),
                        attn_shard=pick_attn_shard(cfg, mesh))
    model = build(cfg, rc)
    params_sds = model.init_eval_shape()
    batch_sds = prefill_batch_specs(cfg, B, S)

    def prefill(params, batch):
        return model.prefill(params, batch)

    if mesh is None:
        return jax.jit(prefill), params_sds, batch_sds, None, model

    p_sh = to_named(param_specs(params_sds, mesh, policy), mesh)
    b_sh = to_named(batch_specs(batch_sds, mesh, policy), mesh)
    jitted = jax.jit(prefill, in_shardings=(p_sh, b_sh), out_shardings=None)
    return jitted, params_sds, batch_sds, p_sh, model


def build_decode_step(cfg, shape_cfg, mesh: Optional[Mesh], *,
                      rc: Optional[RunConfig] = None,
                      policy: Optional[ShardingPolicy] = None,
                      with_stats: bool = False):
    """Decode one token against a cache of shape_cfg.seq_len. The step
    returns (logits, cache), and ``with_stats`` a third output: the MoE
    routing totals as ``moe.<name>`` metrics.

    Returns (jitted, params_sds, cache_sds, batch_sds, shardings, model)."""
    policy = policy or ShardingPolicy()
    rc = rc or RunConfig()
    if mesh is not None:
        rc = rc.replace(constrain=make_constrain(mesh, policy.r()),
                        attn_shard=pick_attn_shard(cfg, mesh))
    model = build(cfg, rc)
    B, S = shape_cfg.global_batch, shape_cfg.seq_len
    params_sds = model.init_eval_shape()
    cache_sds = model.init_cache_eval_shape(B, S)
    batch_sds = decode_batch_specs(cfg, B)

    def decode(params, cache, batch):
        if not with_stats:
            return model.decode(params, cache, batch)
        logits, cache, stats = model.decode_and_stats(params, cache, batch)
        return logits, cache, {"moe." + k: v for k, v in stats.items()}

    if mesh is None:
        jitted = jax.jit(decode, donate_argnums=(1,))
        return jitted, params_sds, cache_sds, batch_sds, None, model

    p_sh = to_named(param_specs(params_sds, mesh, policy), mesh)
    c_sh = to_named(cache_specs(cache_sds, mesh, cfg, shape_cfg, policy), mesh)
    b_sh = to_named(batch_specs(batch_sds, mesh, policy), mesh)
    outs = (None, c_sh, None) if with_stats else (None, c_sh)
    jitted = jax.jit(decode, in_shardings=(p_sh, c_sh, b_sh),
                     out_shardings=outs, donate_argnums=(1,))
    return jitted, params_sds, cache_sds, batch_sds, (p_sh, c_sh, b_sh), model
