"""Distributed train-step builder: pjit + FSDP/TP shardings + grad accum.

``build_train_step`` returns everything the launchers and the dry-run
need: the jitted step, eval-shape stand-ins for state/batch, and the
sharding trees (for device_put / checkpoint restore).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import Model, RunConfig, build
from repro.optim.adamw import OptConfig, TrainState, apply_updates, init_state
from repro.parallel import compression as comp_lib
from repro.parallel.mesh import make_constrain, pick_attn_shard
from repro.parallel.sharding import (ShardingPolicy, batch_specs, param_specs,
                                     to_named)
from repro.runtime.specs import train_batch_specs


@dataclass(frozen=True)
class TrainRunConfig:
    opt: OptConfig = field(default_factory=OptConfig)
    grad_accum: int = 1
    compression: Optional[str] = None    # None | "int8"


def make_train_step(model: Model, trc: TrainRunConfig):
    """Pure train step (no sharding — composable under jit or plain CPU)."""

    def loss_fn(params, batch):
        return model.loss_and_stats(params, batch)

    def train_step(state: TrainState, batch):
        if trc.grad_accum > 1:
            a = trc.grad_accum

            def split(x):
                return x.reshape((a, x.shape[0] // a) + x.shape[1:])

            micro = jax.tree.map(split, batch)

            def acc(carry, mb):
                gsum, lsum = carry
                (l, st), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    state.params, mb)
                gsum = jax.tree.map(
                    lambda s, x: s + x.astype(jnp.float32), gsum, g)
                return (gsum, lsum + l), st

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (gsum, lsum), sts = jax.lax.scan(acc, (zeros, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / a, gsum)
            loss = lsum / a
            stats = jax.tree.map(lambda x: x.sum(0), sts)
        else:
            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, batch)

        if trc.compression == "int8":
            grads = comp_lib.quantize_dequantize_int8(grads)

        new_state, metrics = apply_updates(state, grads, trc.opt)
        metrics["loss"] = loss
        metrics.update({"moe." + k: v for k, v in stats.items()})
        return new_state, metrics

    return train_step


def state_shardings(params_sds, mesh: Mesh, policy: ShardingPolicy):
    p_specs = param_specs(params_sds, mesh, policy)
    return TrainState(params=p_specs,
                      m=jax.tree.map(lambda s: s, p_specs),
                      v=jax.tree.map(lambda s: s, p_specs),
                      step=P())


def build_train_step(cfg, mesh: Optional[Mesh], *, B: int, S: int,
                     rc: Optional[RunConfig] = None,
                     policy: Optional[ShardingPolicy] = None,
                     trc: Optional[TrainRunConfig] = None):
    """Returns (jitted_step, state_sds, batch_sds, state_sh, batch_sh, model)."""
    policy = policy or ShardingPolicy()
    trc = trc or TrainRunConfig()
    rc = rc or RunConfig()
    if mesh is not None:
        rc = rc.replace(constrain=make_constrain(mesh, policy.r()),
                        attn_shard=pick_attn_shard(cfg, mesh))
    model = build(cfg, rc)

    params_sds = model.init_eval_shape()
    state_sds = jax.eval_shape(init_state, params_sds)
    batch_sds = train_batch_specs(cfg, B, S)
    step_fn = make_train_step(model, trc)

    if mesh is None:
        jitted = jax.jit(step_fn, donate_argnums=(0,))
        return jitted, state_sds, batch_sds, None, None, model

    st_sh = to_named(state_shardings(params_sds, mesh, policy), mesh)
    b_sh = to_named(batch_specs(batch_sds, mesh, policy), mesh)
    jitted = jax.jit(step_fn, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None), donate_argnums=(0,))
    return jitted, state_sds, batch_sds, st_sh, b_sh, model


def init_sharded_state(model: Model, mesh: Optional[Mesh], st_sh, seed: int = 0):
    """Initialise TrainState directly into its shardings (no host blowup)."""
    def make():
        return init_state(model.init(jax.random.PRNGKey(seed)))
    if mesh is None:
        return make()
    return jax.jit(make, out_shardings=st_sh)()
