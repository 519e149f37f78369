"""DeepSeek-V2-Lite's layers against the plain reference
(``bench/ref/deepseek_v2.py``), on seeded weights at ``CONFIG.reduced()``
sizes on the CPU: latent attention with YaRN rope, dropless routing over
an expert share, the latent cache, and the planted faults each check
must catch.

The program runs in float32 here, so it and the reference differ only
by summation order: TOL, a relative gap of 1e-4 of the logits' largest
magnitude, is about 100x what a sound program reads (~1e-6) and far
below what any planted fault reads (> 1e-2).
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench.ref import deepseek_v2 as ref  # noqa: E402
from bench.ref import weights as W  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import YarnScaling  # noqa: E402
from repro.models import RunConfig, build  # noqa: E402
from repro.models import attention as attn_lib  # noqa: E402
from repro.models import mla as mla_lib  # noqa: E402
from repro.models import moe as moe_lib  # noqa: E402
from repro.models.layers import yarn_freqs  # noqa: E402
from repro.optim.adamw import OptConfig, init_state  # noqa: E402
from repro.runtime.train import TrainRunConfig, build_train_step  # noqa: E402

TOL = 1e-4
CFG = get_config("deepseek-v2-lite").reduced()
F32 = RunConfig(compute_dtype="float32")


def published(cfg):
    """The reference's model block (published key names) of ``cfg``."""
    ys = cfg.rope_scaling
    return dict(
        hidden_size=cfg.d_model, num_attention_heads=cfg.n_heads,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.norm_eps,
        rope_scaling=None if ys is None else dict(
            type="yarn", factor=ys.factor,
            original_max_position_embeddings=ys.original_max_position,
            beta_fast=ys.beta_fast, beta_slow=ys.beta_slow, mscale=ys.mscale,
            mscale_all_dim=ys.mscale_all_dim),
        num_experts_per_tok=cfg.top_k, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        aux_loss_alpha=cfg.aux_loss_alpha, vocab_size=cfg.vocab_size,
        deployment=dict(expert_offset=cfg.expert_offset))


def seeded(cfg, seed=3):
    model = build(cfg, F32)
    return model, W.init_params(model.init_eval_shape(), jax.random.PRNGKey(seed),
                                n_layers=cfg.n_layers, vocab_size=cfg.vocab_size)


def tokens(shape, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, CFG.vocab_size)


def gap(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


def forward_gap(cfg, params):
    """Program (``cfg``) logits against the reference's (``CFG``)."""
    toks = tokens((2, 32))
    got = jax.jit(lambda p, t: build(cfg, F32).apply(p, {"tokens": t})[0])(
        params, toks)
    want = jax.jit(lambda p, t: ref.serve_logits(p, t, jnp.arange(32),
                                                 published(CFG)))(params, toks)
    return gap(got, want)


def test_train_step_loss_and_gradients_match_the_reference():
    model, params = seeded(CFG)
    batch = W.lm_batch(tokens((2, 33)))
    opt = OptConfig(lr=1e-3, warmup_steps=1, total_steps=10, clip_norm=1e9)
    step, *_ = build_train_step(CFG, None, B=2, S=32, rc=F32,
                                trc=TrainRunConfig(opt=opt))
    state, mets = step(init_state(jax.tree.map(jnp.copy, params)), batch)
    want_l, want_g = ref.make_grad_fn(published(CFG))(params, batch)
    assert abs(float(mets["loss"]) - float(want_l)) <= TOL * abs(float(want_l))
    got_g = jax.tree.map(lambda mm: mm / (1 - opt.b1), state.m)
    gaps = jax.tree.map(lambda a, b: float(jnp.linalg.norm(a - b)
                                           / jnp.linalg.norm(b)), got_g, want_g)
    assert max(jax.tree.leaves(gaps)) <= TOL, gaps
    assert int(mets["moe.slots_held"]) == 2 * 32 * CFG.top_k * (CFG.n_layers - 1)
    assert int(mets["moe.slots_dropped"]) == 0


def test_prefill_then_decode_through_the_latent_cache_matches_full_forward():
    model, params = seeded(CFG)
    toks = tokens((2, 32))
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_and_stats)
    _, pre = prefill(params, {"tokens": toks[:, :24]})
    cache = model.init_cache(2, 32)
    # a stack each for the dense and MoE layers, latent entries only
    assert set(cache) == {"dense", "moe", "pos"}
    for stack, n in (("dense", CFG.first_k_dense),
                     ("moe", CFG.n_layers - CFG.first_k_dense)):
        assert set(cache[stack]) == {"c_kv", "k_pe"}
        assert cache[stack]["c_kv"].shape[0] == n
        assert cache[stack]["c_kv"].shape[-1] + cache[stack]["k_pe"].shape[-1] \
            == CFG.kv_lora_rank + CFG.qk_rope_head_dim
    cache = {k: v if k == "pos" else jax.tree.map(
        lambda c, p: c.at[:, :, :24].set(p), v, pre[k])
        for k, v in cache.items()}
    cache["pos"] = pre["pos"]
    got = []
    for t in range(24, 32):
        lg, cache, stats = decode(params, cache, {"tokens": toks[:, t:t + 1]})
        got.append(lg)
    want = ref.serve_logits(params, toks, jnp.arange(24, 32), published(CFG))
    assert gap(jnp.concatenate(got, 1), want) <= TOL
    assert 0 < int(stats["experts_touched"]) <= (CFG.n_layers - 1) * CFG.n_experts


def test_blocked_causal_attention_matches_one_dense_block():
    """Query blocks scanning key chunks with an online softmax (the 8k
    path, MLA's q/k width 24 and v width 8) against one dense causal
    block, values and gradients."""
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    q, k = (jax.random.normal(kk, (2, 64, 3, 24)) for kk in ks[:2])
    v = jax.random.normal(ks[2], (2, 64, 3, 8))
    cot = jax.random.normal(ks[3], (2, 64, 3, 8))

    def run(attend):
        f = lambda q, k, v: jnp.sum(cot * attend(q, k, v))  # noqa: E731
        return jax.value_and_grad(f, (0, 1, 2))(q, k, v)
    got, got_g = run(lambda q, k, v: attn_lib.chunked_attention(
        q, k, v, chunk=16, scale=0.3))
    want, want_g = run(lambda q, k, v: attn_lib.full_attention(
        q, k, v, causal=True, scale=0.3))
    assert gap(got, want) <= TOL
    for a, b in zip(got_g, want_g):
        assert gap(a, b) <= TOL


def test_yarn_frequencies_and_mscale_by_hand():
    ys = YarnScaling(factor=40.0, original_max_position=4096, beta_fast=32.0,
                     beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)
    got = np.asarray(yarn_freqs(64, 10_000.0, ys), np.float64)
    # low = floor(64 ln(4096 / (32 2pi)) / (2 ln 1e4)) = floor(10.47) = 10,
    # high = ceil(64 ln(4096 / 2pi) / (2 ln 1e4)) = ceil(22.51) = 23
    plain = 10_000.0 ** (-np.arange(0, 64, 2) / 64)
    keep = 1 - np.clip((np.arange(32) - 10) / 13, 0, 1)
    assert np.allclose(got[:11], plain[:11], rtol=1e-6)        # extrapolated
    assert np.allclose(got[23:], plain[23:] / 40, rtol=1e-6)   # interpolated
    assert np.allclose(got, plain / 40 * (1 - keep) + plain * keep, rtol=1e-6)
    assert got[16] == pytest.approx(plain[16] * (7 / 13 + 6 / 13 / 40), rel=1e-6)
    # s = 192^-1/2 (0.1 * 0.707 * ln 40 + 1)^2 = 0.07216878 * 1.26080378^2
    full = get_config("deepseek-v2-lite")
    assert mla_lib.softmax_scale(full) == pytest.approx(0.11472139, rel=1e-6)
    assert mla_lib.rope_terms(full)[1] == 1.0
    assert ref.softmax_scale(published(full)) == mla_lib.softmax_scale(full)


def _moe_layer(cfg, p, x):
    return jax.jit(lambda p, x: moe_lib.apply_moe(p, x, cfg, F32))(p, x)


def test_expert_shares_add_up_to_the_uncut_reference_layer():
    """Shares 0..7 of 8 chips, each holding E/8 experts, with the shared
    experts counted once, add up to the reference's uncut layer."""
    _, params = seeded(CFG)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 32, CFG.d_model))
    n = CFG.n_experts // 8
    total = 0.0
    for j in range(8):
        cfg = dataclasses.replace(CFG, n_experts_held=n, expert_offset=j * n)
        share = {k: v[j * n:(j + 1) * n] for k, v in p.items()
                 if k in ("w1", "w3", "w2")}
        share["router"] = p["router"]
        if j == 0:
            share["shared"] = p["shared"]
        y, _, stats = _moe_layer(cfg, share, x)
        assert int(stats["slots_dropped"]) == 0
        total = total + y
    want, _ = ref.moe(x, p, published(CFG), None)
    assert gap(total, want) <= TOL
    uncut, _, _ = _moe_layer(CFG, p, x)
    assert gap(uncut, want) <= TOL


def test_no_slot_is_dropped_under_a_skewed_router():
    _, params = seeded(CFG)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"]["moe"])
    # every token's top experts are 0 .. top_k - 1
    p = dict(p, router=p["router"].at[:, :CFG.top_k].set(1.0))
    x = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(6), (2, 32, CFG.d_model))
    y, _, stats = _moe_layer(CFG, p, x)
    assert int(stats["load_max"]) == 64 and int(stats["slots_held"]) == 64 * 6
    assert int(stats["slots_dropped"]) == 0
    assert int(stats["experts_touched"]) == CFG.top_k
    want, _ = ref.moe(x, p, published(CFG), None)
    assert gap(y, want) <= TOL
    # the GShard path's capacity drops slots under the same router
    gshard = dataclasses.replace(CFG, moe_dropless=False)
    _, _, stats = _moe_layer(gshard, p, x)
    assert int(stats["slots_dropped"]) > 0


def test_rows_past_the_groups_are_never_read(monkeypatch):
    """On the TPU the grouped product leaves the rows past its groups
    unwritten, in its output and in its input's gradient. Filled with
    NaN here, the layer's output and gradients still match."""
    real = jax.lax.ragged_dot

    @jax.custom_vjp
    def unwritten(x, w, sizes):
        return _nan_past(real(x, w, sizes), sizes)

    def fwd(x, w, sizes):
        return unwritten(x, w, sizes), (x, w, sizes)

    def bwd(res, g):
        x, w, sizes = res
        _, vjp = jax.vjp(lambda x, w: real(x, w, sizes), x, w)
        dx, dw = vjp(g)
        return _nan_past(dx, sizes), dw, None
    unwritten.defvjp(fwd, bwd)
    monkeypatch.setattr(jax.lax, "ragged_dot", unwritten)
    _, params = seeded(CFG)
    p = jax.tree.map(lambda a: a[0], params["blocks"]["moe"]["moe"])
    share = dataclasses.replace(CFG, n_experts_held=4, expert_offset=4)
    p = {k: v[4:8] if k in ("w1", "w3", "w2") else v for k, v in p.items()}
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, CFG.d_model))
    m = published(share)
    want = ref.moe(x, p, m, None)[0]
    assert gap(_moe_layer(share, p, x)[0], want) <= TOL
    got_g = jax.grad(lambda p, x: jnp.sum(
        moe_lib.apply_moe(p, x, share, F32)[0] ** 2), (0, 1))(p, x)
    want_g = jax.grad(lambda p, x: jnp.sum(
        ref.moe(x, p, m, None)[0] ** 2), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert gap(a, b) <= TOL


def _nan_past(y, sizes):
    return jnp.where((jnp.arange(y.shape[0]) < sizes.sum())[:, None], y,
                     jnp.nan)


def test_sound_program_is_within_tolerance():
    _, params = seeded(CFG)
    assert forward_gap(CFG, params) <= TOL


def _no_kv_norm(monkeypatch):
    monkeypatch.setattr(mla_lib, "rms_norm", lambda x, g, eps: x)
    return CFG


FAULTS = {
    "top5_routing": lambda mp: dataclasses.replace(CFG, top_k=CFG.top_k - 1),
    "renormalised_gates": lambda mp: dataclasses.replace(CFG, norm_topk_prob=True),
    "plain_rope": lambda mp: dataclasses.replace(CFG, rope_scaling=None),
    "no_kv_a_layernorm": _no_kv_norm,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_fails_the_tolerance(fault, monkeypatch):
    _, params = seeded(CFG)
    assert forward_gap(FAULTS[fault](monkeypatch), params) > 100 * TOL
