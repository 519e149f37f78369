"""Plain float32 reference of the Qwen2 decoder (arXiv:2407.10671) and
of the AdamW step the configuration states.

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no batching tricks. Layers run under ``jax.checkpoint`` so that
a row's gradient fits next to the optimizer state; that changes what is
stored, not what is computed. It reads weights by the names of the
benchmark's weight tree (``bench/ref/weights.py``):

    embed (V, D), final_norm (D,), blocks/{ln1, ln2, attn/{wq, wk, wv,
    wo, bq, bk, bv}, mlp/{w1, w3, w2}} stacked over a leading layer axis

Departures from the published model, shared with the program: an RMS
norm's weight is stored as an offset ``g`` and applied as ``1 + g``;
the embedding has padding rows past ``vocab_size`` that the loss masks.

``operand_dtype`` rounds both operands of every matmul to a lower
precision (one per-tensor scale each): the control that ``correct``
must reject.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _quant(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _dot(spec, a, b, operand_dtype):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if operand_dtype is None:
        return jnp.einsum(spec, a, b, precision=HI)
    return _low_dot(spec, operand_dtype)(a, b)


def _low_dot(spec, dtype):
    """einsum with both operands rounded to ``dtype``, forward and
    backward: the backward pass rounds the incoming gradient as well."""
    def dot(a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    @jax.custom_vjp
    def low(a, b):
        return dot(_quant(a, dtype), _quant(b, dtype))

    def fwd(a, b):
        qa, qb = _quant(a, dtype), _quant(b, dtype)
        return dot(qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(dot, *res)
        return vjp(_quant(g, dtype))
    low.defvjp(fwd, bwd)
    return low


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + g)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs           # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(h, p, *, m, dt):
    """One decoder layer over h (B, S, D), all positions, causal."""
    B, S, _ = h.shape
    H, K, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    a = p["attn"]
    x = _rms(h, p["ln1"], m["rms_norm_eps"])
    q = _dot("bsd,df->bsf", x, a["wq"], dt) + a["bq"]
    k = _dot("bsd,df->bsf", x, a["wk"], dt) + a["bk"]
    v = _dot("bsd,df->bsf", x, a["wv"], dt) + a["bv"]
    pos = jnp.arange(S)
    q = _rope(q.reshape(B, S, H, hd), pos, m["rope_theta"])
    k = _rope(k.reshape(B, S, K, hd), pos, m["rope_theta"])
    v = v.reshape(B, S, K, hd)
    k = jnp.repeat(k, H // K, axis=2)           # head i reads kv head i // G
    v = jnp.repeat(v, H // K, axis=2)
    s = _dot("bshd,bthd->bhst", q, k, dt) / math.sqrt(hd)
    s = jnp.where(pos[:, None] >= pos[None, :], s, NEG)
    o = _dot("bhst,bthd->bshd", jax.nn.softmax(s, -1), v, dt)
    h = h + _dot("bsf,fd->bsd", o.reshape(B, S, H * hd), a["wo"], dt)
    f = p["mlp"]
    x = _rms(h, p["ln2"], m["rms_norm_eps"])
    g = jax.nn.silu(_dot("bsd,df->bsf", x, f["w1"], dt))
    u = _dot("bsd,df->bsf", x, f["w3"], dt)
    return h + _dot("bsf,fd->bsd", g * u, f["w2"], dt)


def hidden(params, tokens, m, dt=None):
    """Final-normed hidden states (B, S, D) in float32."""
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    h = jnp.take(f32["embed"], tokens, axis=0)
    body = jax.checkpoint(partial(_layer, m=m, dt=dt))

    def step(h, p):
        return body(h, p), None
    h, _ = jax.lax.scan(step, h, f32["blocks"])
    return _rms(h, f32["final_norm"], m["rms_norm_eps"])


def logits(params, h, dt=None):
    return _dot("bsd,vd->bsv", h, params["embed"], dt)


def loss(params, batch, m, dt=None):
    """Mean next-token cross entropy over every position; logits of the
    embedding's padding rows are masked out."""
    lg = logits(params, hidden(params, batch["tokens"], m, dt), dt)
    lg = jnp.where(jnp.arange(lg.shape[-1]) < m["vocab_size"], lg, -1e9)
    logz = jax.scipy.special.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# AdamW as the configuration states it
# ---------------------------------------------------------------------------
def lr_at(step, o):
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay
    to ``min_lr_ratio * lr`` at ``total_steps``."""
    step = float(step)
    if step < o["warmup_steps"]:
        return o["lr"] * step / max(o["warmup_steps"], 1)
    prog = (step - o["warmup_steps"]) / max(o["total_steps"] - o["warmup_steps"], 1)
    prog = min(max(prog, 0.0), 1.0)
    r = o["min_lr_ratio"]
    return o["lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * prog)))


@partial(jax.jit, static_argnames=("o",), donate_argnums=(0, 2, 3))
def _adamw(params, grads, m, v, scale, lr, b1c, b2c, o):
    o = dict(o)

    def upd(p, g, mm, vv):
        g = g * scale
        mm = o["b1"] * mm + (1 - o["b1"]) * g
        vv = o["b2"] * vv + (1 - o["b2"]) * g * g
        d = (mm / b1c) / (jnp.sqrt(vv / b2c) + o["eps"])
        if p.ndim >= o["decay_min_ndim"]:
            d = d + o["weight_decay"] * p
        return p - lr * d, mm, vv
    out = jax.tree.map(upd, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)


def adamw_step(params, grads, m, v, step: int, o: dict):
    """One step (``step`` counts from 1): clip by global norm, then
    AdamW with decoupled weight decay on every stored leaf of at least
    ``decay_min_ndim`` dimensions. Returns (params, m, v, clipped grads'
    per-leaf norms)."""
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(g * g)) * scale, grads)
    params, m, v = _adamw(params, grads, m, v, scale, lr_at(step, o),
                          1 - o["b1"] ** step, 1 - o["b2"] ** step,
                          tuple(sorted(o.items())))
    return params, m, v, norms


# ---------------------------------------------------------------------------
# What the check compares
# ---------------------------------------------------------------------------
def make_grad_fn(m, dt=None):
    return jax.jit(jax.value_and_grad(lambda p, b: loss(p, b, m, dt)))


@partial(jax.jit, donate_argnums=(0,))
def _acc(total, g):
    return jax.tree.map(jnp.add, total, g)


def batch_grad(grad_fn, params, batch, rows: Optional[int] = None):
    """Mean loss and gradient over the batch's first ``rows`` rows (all
    by default), one row at a time."""
    n = batch["tokens"].shape[0] if rows is None else rows
    total_l, total_g = 0.0, None
    for r in range(n):
        row = jax.tree.map(lambda x: x[r:r + 1], batch)
        lv, g = grad_fn(params, row)
        total_l = total_l + lv
        total_g = g if total_g is None else _acc(total_g, g)
    return total_l / n, jax.tree.map(lambda g: g / n, total_g)


def train_readings(params, batches, grad_fn, o, rows=None):
    """Run ``len(batches)`` AdamW steps from ``params`` (consumed), with
    gradients from ``grad_fn`` (``make_grad_fn``). Returns the losses,
    the per-leaf norms of the first clipped gradient and the final
    parameters."""
    mm = jax.tree.map(jnp.zeros_like, params)
    vv = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for i, batch in enumerate(batches):
        lv, g = batch_grad(grad_fn, params, batch, rows)
        params, mm, vv, norms = adamw_step(params, g, mm, vv, i + 1, o)
        losses.append(float(lv))
        if first is None:
            first = jax.tree.map(float, norms)
        del g
    return losses, first, params


def serve_logits(params, tokens, positions, m, dt=None):
    """Logits (B, len(positions), V) of every padded-vocabulary row at
    the given positions of ``tokens`` (B, S)."""
    h = hidden(params, tokens, m, dt)[:, positions]
    return logits(params, h, dt)
