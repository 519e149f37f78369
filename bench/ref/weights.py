"""Weights and token streams made from the seed, on the device.

Both are the benchmark's own: the program is handed them, and the plain
reference rebuilds them from the same seed. The weights fill whatever
tree of shapes the program's model declares, leaf by leaf by name.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any seed up to 64 bits."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "name", last)))


def init_params(shapes, key, *, n_layers: int, vocab_size: int):
    """Random weights for the tree of ShapeDtypeStructs ``shapes``.

    Matrices: truncated normal over fan-in (the output projections
    scaled by 1/sqrt(2 n_layers)); the embedding N(0, 0.02) with its
    padding rows (index >= vocab_size) zero; norm offsets N(0, 0.1);
    biases N(0, 0.02). A leading layer axis under ``blocks`` is a stack.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, sds) in enumerate(leaves):
        name = _leaf_name(path)
        k = jax.random.fold_in(key, i)
        stacked = any(_leaf_name(path[:j + 1]) == "blocks"
                      for j in range(len(path) - 1))
        ndim = len(sds.shape) - (1 if stacked else 0)
        if name == "embed":
            x = jax.random.normal(k, sds.shape, jnp.float32) * 0.02
            rows = jnp.arange(sds.shape[0])[:, None] < vocab_size
            x = jnp.where(rows, x, 0.0)
        elif ndim >= 2:
            fan_in = sds.shape[-2]
            std = 1.0 / jnp.sqrt(jnp.float32(fan_in))
            if name in ("wo", "w2"):
                std = std / jnp.sqrt(jnp.float32(2 * n_layers))
            x = jax.random.truncated_normal(k, -2.0, 2.0, sds.shape,
                                            jnp.float32) * std
        elif name.startswith("ln") or name.endswith("norm"):
            x = jax.random.normal(k, sds.shape, jnp.float32) * 0.1
        else:
            x = jax.random.normal(k, sds.shape, jnp.float32) * 0.02
        out.append(x.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def tokens(key, workflow: int, shape, vocab_size: int):
    """Token ids for one workflow's stage, uniform over the vocabulary."""
    k = jax.random.fold_in(key, workflow)
    return jax.random.randint(k, shape, 0, vocab_size, jnp.int32)


def lm_batch(toks):
    """(..., S + 1) ids -> next-token inputs and labels of length S."""
    return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
