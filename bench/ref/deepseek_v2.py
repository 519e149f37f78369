"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434; HF
``deepseek_v2``) with ``q_lora_rank`` null, for one chip's share of the
experts; the AdamW step the configuration states is
``bench/ref/qwen2.py``'s (``train_readings``).

Straightforward ``jax.numpy`` at ``Precision.HIGHEST``: no kernels, no
cache, no capacity and no grouping. Per layer:

    MLA     q = x W_q -> H x (q_nope 128 | q_pe 64); [c | k_pe] = x W_kva;
            c = RMSNorm(c); [k_nope | v] = c W_kvb -> H x (128 | 128);
            q_pe and the one shared k_pe rotated with YaRN frequencies;
            causal softmax(s q.k) v, s = 192^-1/2 mscale(40, 0.707)^2; W_o
    MLP     layer 0 (first_k_dense): SwiGLU of width intermediate_size
    MoE     scores = softmax(x W_g) over every expert, greedy top-k,
            weights = the top-k scores (renormalised only with
            norm_topk_prob) x routed_scaling_factor; y = sum over the
            *held* experts e of w_e E_e(x), each held expert computed on
            every token and weighted by its gate (0 where not chosen),
            plus the shared experts' SwiGLU
    loss    next-token cross entropy + aux_loss_alpha x the sum over MoE
            layers of the sequence-level balance loss over all experts

It reads weights by the names of the program's tree, filled by
``bench/ref/weights.py``: embed (V, D), head (V, D), final_norm (D,),
blocks/{dense, moe}/{ln1, ln2, attn/{wq, wkv_a, kv_norm, wkv_b, wo},
mlp/{w1, w3, w2} | moe/{router, w1, w3, w2, shared/{w1, w3, w2}}},
stacked over a leading layer axis. ``m`` is the configuration's model
block by the published key names, with ``deployment.expert_offset`` the
first expert held.

Departures from the published model, shared with the program: an RMS
norm's weight is stored as an offset ``g`` and applied as ``1 + g``;
the rope dims are rotated as halves (the published code de-interleaves
them first: with seed-drawn weights, a fixed permutation of W_q's and
W_kva's rope columns). To fit at the timed sizes, attention runs in
blocks of query rows against every key (masked), one block after the
other and each rematerialised, and every layer is rematerialised: that
changes what is stored and when, not what is computed.

``operand_dtype`` rounds both operands of every matmul to a lower
precision (``bench/ref/qwen2.py``): the control that ``correct`` must
reject.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from bench.ref.qwen2 import _dot, _rms, train_readings  # noqa: F401

NEG = -1e30
Q_BLOCK = 512


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, rs):
    """DeepSeek-V2's YaRN frequencies (``rope_scaling`` of the config)."""
    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = extra / rs["factor"]
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def softmax_scale(m):
    s = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    rs = m.get("rope_scaling")
    if rs and rs.get("mscale_all_dim"):
        f = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        s = s * f * f
    return s


def _rope(x, pos, m):
    """x (..., S, heads, dr) rotated as halves at positions ``pos`` (S,)."""
    dr, rs = x.shape[-1], m.get("rope_scaling")
    if rs:
        freqs = yarn_inv_freq(dr, m["rope_theta"], rs)
        msc = (yarn_mscale(rs["factor"], rs["mscale"])
               / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    else:
        freqs = 1.0 / m["rope_theta"] ** (
            jnp.arange(0, dr, 2, dtype=jnp.float32) / dr)
        msc = 1.0
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :] * msc, jnp.sin(ang)[:, None, :] * msc
    x1, x2 = x[..., :dr // 2], x[..., dr // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _swiglu(x, f, dt):
    g = jax.nn.silu(_dot("bsd,df->bsf", x, f["w1"], dt))
    return _dot("bsf,fd->bsd", g * _dot("bsd,df->bsf", x, f["w3"], dt),
                f["w2"], dt)


def _attn_rows(q, k, v, a, scale, dt):
    """Query rows from ``a`` (q (B,s,H,dq)) against every key, causal."""
    s = _dot("bshd,bthd->bhst", q, k, dt) * scale
    qpos = a + jnp.arange(q.shape[1])
    s = jnp.where(qpos[:, None] >= jnp.arange(k.shape[1])[None, :], s, NEG)
    return _dot("bhst,bthd->bshd", jax.nn.softmax(s, -1), v, dt)


def _mla(x, a, m, dt):
    B, S, _ = x.shape
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    pos = jnp.arange(S)
    q = _dot("bsd,df->bsf", x, a["wq"], dt).reshape(B, S, H, dn + dr)
    kva = _dot("bsd,df->bsf", x, a["wkv_a"], dt)
    c = _rms(kva[..., :r], a["kv_norm"], m["rms_norm_eps"])
    k_pe = _rope(kva[..., None, r:], pos, m)                    # (B,S,1,dr)
    kv = _dot("bsc,cf->bsf", c, a["wkv_b"], dt).reshape(B, S, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], pos, m)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_pe, (B, S, H, dr))], -1)
    v = kv[..., dn:]
    scale = softmax_scale(m)
    outs = []
    for a0 in range(0, S, Q_BLOCK):
        q_i = q[:, a0:a0 + Q_BLOCK]
        if outs:       # one block after the other, in both passes
            outs[-1], q_i, k, v = jax.lax.optimization_barrier(
                (outs[-1], q_i, k, v))
        rows = jax.checkpoint(partial(_attn_rows, a=a0, scale=scale, dt=dt))
        outs.append(rows(q_i, k, v))
    o = jnp.concatenate(outs, 1).reshape(B, S, H * dv)
    return _dot("bsf,fd->bsd", o, a["wo"], dt)


def route(x, router, m):
    """(scores (B,S,E), gates (B,S,E): the top-k weights, 0 elsewhere,
    chosen (B,S,E): 1 where an expert is among the top-k)."""
    scores = jax.nn.softmax(_dot("bsd,de->bse", x, router, None), -1)
    k = m["num_experts_per_tok"]
    top, idx = jax.lax.top_k(scores, k)
    if m["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * m["routed_scaling_factor"]
    onehot = jax.nn.one_hot(idx, scores.shape[-1])               # (B,S,k,E)
    return scores, jnp.sum(onehot * top[..., None], -2), onehot.sum(-2)


def seq_aux(scores, chosen, m):
    """DeepSeek-V2's sequence-level balance loss over every expert."""
    S, E = scores.shape[1], scores.shape[-1]
    load = chosen.sum(1) / (S * m["num_experts_per_tok"] / E)    # (B, E)
    return jnp.mean(jnp.sum(load * scores.mean(1), -1))


def moe(x, p, m, dt):
    """(y, aux): the held experts' part plus the shared experts."""
    scores, gates, chosen = route(x, p["router"], m)
    off = m.get("deployment", {}).get("expert_offset", 0)
    y = _swiglu(x, p["shared"], dt)
    for e in range(p["w1"].shape[0]):
        ex = {"w1": p["w1"][e], "w3": p["w3"][e], "w2": p["w2"][e]}
        y = y + gates[..., off + e, None] * _swiglu(x, ex, dt)
    return y, seq_aux(scores, chosen, m)


def _layer(carry, p, *, m, dt):
    h, aux = carry
    eps = m["rms_norm_eps"]
    h = h + _mla(_rms(h, p["ln1"], eps), p["attn"], m, dt)
    x = _rms(h, p["ln2"], eps)
    if "moe" in p:
        y, a = moe(x, p["moe"], m, dt)
        return h + y, aux + a
    return h + _swiglu(x, p["mlp"], dt), aux


def hidden(params, tokens, m, dt=None):
    """(final-normed hidden states (B, S, D), summed balance loss)."""
    f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    carry = (jnp.take(f32["embed"], tokens, axis=0), jnp.zeros(()))
    body = jax.checkpoint(partial(_layer, m=m, dt=dt))

    def step(c, p):
        return body(c, p), None
    for kind in ("dense", "moe"):
        if kind in f32["blocks"]:
            carry, _ = jax.lax.scan(step, carry, f32["blocks"][kind])
    h, aux = carry
    return _rms(h, f32["final_norm"], m["rms_norm_eps"]), aux


def logits(params, h, dt=None):
    return _dot("bsd,vd->bsv", h, params["head"], dt)


def loss(params, batch, m, dt=None):
    """Mean next-token cross entropy + aux_loss_alpha x balance loss."""
    h, aux = hidden(params, batch["tokens"], m, dt)
    lg = logits(params, h, dt)
    logz = jax.scipy.special.logsumexp(lg, -1)
    gold = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(logz - gold) + m["aux_loss_alpha"] * aux


def make_grad_fn(m, dt=None):
    return jax.jit(jax.value_and_grad(lambda p, b: loss(p, b, m, dt)))


def serve_logits(params, tokens, positions, m, dt=None):
    """Logits (B, len(positions), V) at the given positions of ``tokens``."""
    h, _ = hidden(params, tokens, m, dt)
    return logits(params, h[:, positions], dt)
