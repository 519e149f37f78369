"""Mixture-of-Experts layer: routed top-k experts + shared experts.

Two dispatch paths, picked by ``cfg.moe_dropless``.

Dropless (DeepSeek-V2): softmax router in float32 over every expert,
greedy top-k, gates renormalised only if ``cfg.norm_topk_prob``, times
``cfg.routed_scaling_factor``. The layer holds ``cfg.n_experts_local``
experts, ids ``expert_offset ..``, the chip's share under expert
parallelism: it routes over all experts and computes its own experts'
part for every slot routed to them, sorted by expert into one grouped
product (``jax.lax.ragged_dot``), plus the shared experts. What the
absent experts add is left out; no exchange is stood in for. Balance
loss: Switch-style, or DeepSeek's sequence-level one (``moe_aux``).

GShard (the default): token-choice top-k routing with a
per-group expert capacity; dispatch and combine are one-hot einsums so
the layer lowers to plain dot_generals + the collectives XLA SPMD picks
for the (tokens: data-sharded) x (experts: model-sharded) contraction.
This compiles robustly on every mesh (the design baseline); a ragged
all-to-all variant is an explicitly-recorded §Perf hillclimb item.

Experts are padded to a multiple of 16 (``cfg.n_experts_padded``) so EP
shards evenly; pad experts receive -inf router logits and zero capacity
use.
"""
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from repro.models.layers import RunConfig, dense_init, init_mlp, apply_mlp

# Per-step int32 totals a layer returns beside its aux loss, summed over
# layers: slots routed to the experts held here, of those the slots not
# computed (capacity), each layer's largest held-expert load, and the
# held experts that received any slot.
STAT_KEYS = ("slots_held", "slots_dropped", "load_max", "experts_touched")


def zero_stats() -> Dict[str, jax.Array]:
    return {k: jnp.zeros((), jnp.int32) for k in STAT_KEYS}


def init_moe(key, cfg, dtype):
    d, f, Ep = cfg.d_model, cfg.expert_d_ff, cfg.n_experts_padded
    El = cfg.n_experts_local
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], (d, Ep), jnp.float32),
        "w1": dense_init(ks[1], (El, d, f), dtype),
        "w3": dense_init(ks[2], (El, d, f), dtype),
        "w2": dense_init(ks[3], (El, f, d), dtype, scale=1.0 / (2 * cfg.n_layers) ** 0.5),
    }
    if cfg.shared_expert_d_ff:
        p["shared"] = init_mlp(ks[4], d, cfg.shared_expert_d_ff, dtype)
    return p


def _capacity(cfg, group: int) -> int:
    c = int(cfg.top_k * group / cfg.n_experts * cfg.capacity_factor)
    return max(4, (c + 3) // 4 * 4)


def route(logits_f32, cfg, group: int):
    """Top-k routing with capacity. logits: (G, S, Ep) f32.

    Returns (dispatch (G,S,E,C) bf16, combine (G,S,E,C) f32-weights,
    aux_loss scalar).
    """
    E, Ep, k = cfg.n_experts, cfg.n_experts_padded, cfg.top_k
    C = _capacity(cfg, group)
    if Ep > E:  # padded experts never routable
        pad = jnp.arange(Ep) >= E
        logits_f32 = jnp.where(pad, -1e9, logits_f32)
    probs = jax.nn.softmax(logits_f32, axis=-1)                  # (G,S,Ep)
    gate_vals, idx = jax.lax.top_k(probs, k)                     # (G,S,k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    G, S, _ = probs.shape
    dispatch = jnp.zeros((G, S, Ep, C), jnp.bfloat16)
    combine = jnp.zeros((G, S, Ep, C), jnp.float32)
    counts = jnp.zeros((G, Ep), jnp.int32)
    for slot in range(k):                                        # k <= 4, unrolled
        oh = jax.nn.one_hot(idx[:, :, slot], Ep, dtype=jnp.int32)    # (G,S,Ep)
        pos = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]       # rank in queue
        keep = (pos < C) & (oh > 0)
        pos_oh = jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=jnp.float32)
        sel = (keep.astype(jnp.float32))[..., None] * pos_oh         # (G,S,Ep,C)
        dispatch = dispatch + sel.astype(jnp.bfloat16)
        combine = combine + sel * gate_vals[:, :, slot, None, None]
        counts = counts + oh.sum(axis=1)

    # load-balancing aux loss (Switch-style), over real experts only
    me = probs[..., :E].mean(axis=(0, 1))
    assign = dispatch[..., :E, :].astype(jnp.float32).sum(-1).mean(axis=(0, 1))
    aux = E * jnp.sum(me * assign)
    return dispatch, combine, aux


def apply_moe(params, x, cfg, rc: RunConfig):
    """x: (B,S,D) -> (y, aux_loss, stats)."""
    if cfg.moe_dropless:
        return _apply_dropless(params, x, cfg, rc)
    if cfg.n_experts_held:
        raise NotImplementedError("an expert share needs moe_dropless")
    B, S, D = x.shape
    tokens = B * S
    group = min(rc.moe_group, tokens)
    G = tokens // group
    assert G * group == tokens, (tokens, group)
    xg = x.reshape(G, group, D)

    logits = jnp.einsum("gsd,de->gse", xg, params["router"].astype(rc.cdtype))
    dispatch, combine, aux = route(logits.astype(jnp.float32), cfg, group)

    # NOTE(§Perf, refuted): constraining xe/he to an expert-sharded layout
    # here ("dp","tp",None,None) doubled collective bytes on the 16x16
    # mesh — resharding the (G,E,C,D) tensors costs more than the
    # all-reduce XLA picks on its own. Left unconstrained deliberately.
    xe = jnp.einsum("gsec,gsd->gecd", dispatch, xg)              # (G,E,C,D)
    h1 = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, params["w1"]))
    h3 = jnp.einsum("gecd,edf->gecf", xe, params["w3"])
    he = jnp.einsum("gecf,efd->gecd", h1 * h3, params["w2"])     # (G,E,C,D)
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(he.dtype), he)

    if "shared" in params:
        y = y + apply_mlp(params["shared"], xg)
    kept = dispatch.astype(jnp.int32).sum(axis=(0, 1, 3))       # (Ep,)
    routed = jnp.asarray(tokens * cfg.top_k, jnp.int32)
    stats = {"slots_held": routed,
             "slots_dropped": routed - kept.sum(),
             "load_max": kept.max(),
             "experts_touched": (kept > 0).sum().astype(jnp.int32)}
    return y.reshape(B, S, D), aux, stats


# ---------------------------------------------------------------------------
# Dropless path
# ---------------------------------------------------------------------------
@jax.custom_vjp
def _permute(x, perm, inv):
    """Rows of x in the order ``perm`` (``inv`` its inverse). The
    backward pass is the gather by ``inv``, not a scatter-add."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inv):
    return jnp.take(x, perm, axis=0), (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _aux_loss(probs, idx, cfg, B, S):
    """probs (T, E) f32, idx (T, k) of T = B*S tokens."""
    E, k = cfg.n_experts, cfg.top_k
    hits = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(1)      # (T, E)
    if cfg.moe_aux == "seq":
        # DeepSeek-V2 seq_aux: per sequence, expert load (normalised so
        # an even split reads 1) times mean score, summed over experts
        ce = hits.reshape(B, S, E).sum(1) / (S * k / E)
        return jnp.mean(jnp.sum(ce * probs.reshape(B, S, E).mean(1), -1))
    return E * jnp.sum(probs.mean(0) * hits.mean(0))


def _routed(params, xt, idx, gates, cfg):
    """The held experts' part for tokens xt (T, D) routed to ``idx``
    (T, k) with weights ``gates``: every slot held here is computed.
    Returns (y (T, D), held slots, slots not computed, per-expert load)."""
    T, D = xt.shape
    k, El = cfg.top_k, cfg.n_experts_local
    # slots held here, sorted by expert; the others sort last, unused
    local = idx.reshape(-1) - cfg.expert_offset                  # (T*k,)
    held = (local >= 0) & (local < El)
    key = jnp.where(held, local, El)
    perm = jnp.argsort(key, stable=True)
    inv = jnp.zeros_like(perm).at[perm].set(jnp.arange(T * k, dtype=perm.dtype))
    sizes = jnp.zeros((El + 1,), jnp.int32).at[key].add(1)[:El]
    n_rows = sizes.sum()
    # rows past the groups belong to no expert held here. The grouped
    # product does not compute them: on the TPU it leaves them unwritten,
    # in its output and in its input's gradient, so both are masked
    # (the mask on the input is for its gradient)
    in_group = (jnp.arange(T * k) < n_rows)[:, None]
    rows = _permute(jnp.repeat(xt, k, axis=0), perm, inv)        # (T*k, D)
    rows = jnp.where(in_group, rows, 0)
    h = jax.nn.silu(jax.lax.ragged_dot(rows, params["w1"], sizes)) \
        * jax.lax.ragged_dot(rows, params["w3"], sizes)
    out = jax.lax.ragged_dot(h, params["w2"], sizes)             # (T*k, D)
    out = jnp.where(in_group, out, 0)
    out = _permute(out, inv, perm).reshape(T, k, D)
    w = jnp.where(held.reshape(T, k), gates, 0.0).astype(out.dtype)
    y = jnp.einsum("tkd,tk->td", out, w, preferred_element_type=jnp.float32)
    n_held = held.sum().astype(jnp.int32)
    return y.astype(xt.dtype), n_held, n_held - n_rows, sizes


def _apply_dropless(params, x, cfg, rc: RunConfig):
    """Tokens are routed in groups of ``rc.moe_group``, each group's
    grouped product rematerialised, so one group's slot buffers are live
    at a time."""
    B, S, D = x.shape
    T, k = B * S, cfg.top_k
    xt = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"],
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                      # (T, E)
    gates, idx = jax.lax.top_k(probs, k)                         # greedy
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-20)
    if cfg.routed_scaling_factor != 1.0:
        gates = gates * cfg.routed_scaling_factor

    group = min(rc.moe_group, T)
    G = T // group
    assert G * group == T, (T, group)
    experts = {n: params[n] for n in ("w1", "w3", "w2")}
    routed = jax.checkpoint(partial(_routed, cfg=cfg))
    y, n_held, dropped, sizes = jax.lax.map(
        lambda a: routed(experts, *a),
        (xt.reshape(G, group, D), idx.reshape(G, group, k),
         gates.reshape(G, group, k)))
    y = y.reshape(B, S, D)
    if "shared" in params:
        y = y + apply_mlp(params["shared"], x)
    load = sizes.sum(0)
    stats = {"slots_held": n_held.sum(), "slots_dropped": dropped.sum(),
             "load_max": load.max(),
             "experts_touched": (load > 0).sum().astype(jnp.int32)}
    return y, _aux_loss(probs, idx, cfg, B, S), stats
