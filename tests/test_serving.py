"""Serving correctness: incremental decode must match full-sequence
forward (the strongest cache-correctness property), per family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import RunConfig, build, transformer

# one representative per family
FAMILY_REPS = ["qwen2-0.5b", "qwen2-moe-a2.7b", "mamba2-2.7b",
               "zamba2-1.2b", "musicgen-medium", "llama-3.2-vision-11b"]


@pytest.mark.parametrize("arch", FAMILY_REPS)
def test_incremental_decode_matches_forward(arch):
    import dataclasses
    cfg = get_config(arch).reduced()
    if cfg.n_experts:
        # capacity drops are a train-time batching artifact; the
        # decode-equivalence property needs drop-free routing
        cfg = dataclasses.replace(cfg, capacity_factor=16.0)
    rc = RunConfig(param_dtype="float32", compute_dtype="float32")
    model = build(cfg, rc)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    key = jax.random.PRNGKey(1)
    if cfg.frontend == "audio":
        embeds = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        full, _, _ = model.apply(params, {"embeds": embeds})
        cache = model.init_cache(B, S)
        outs = []
        for t in range(S):
            logits, cache = model.decode(params, cache,
                                         {"embeds": embeds[:, t:t + 1]})
            outs.append(logits)
    else:
        tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        batch = {"tokens": tokens}
        if cfg.frontend == "vision":
            img = jax.random.normal(key, (B, cfg.n_img_tokens, cfg.d_model),
                                    jnp.float32)
            batch["img_embeds"] = img
            # vision decode needs the cross-KV cache -> prefill first then
            # compare the decode continuation against forward on S+1
            logits_full, cache = model.prefill(params, batch)
            nxt = jnp.ones((B, 1), jnp.int32)
            tokens2 = jnp.concatenate([tokens, nxt], axis=1)
            full2, _, _ = model.apply(params, {"tokens": tokens2,
                                               "img_embeds": img})
            pad = ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
            cache["k"] = jnp.pad(cache["k"], pad)   # room for the new token
            cache["v"] = jnp.pad(cache["v"], pad)
            dec, cache = model.decode(params, cache, {"tokens": nxt})
            err = jnp.abs(dec[:, 0] - full2[:, -1]).max()
            assert float(err) < 2e-3, float(err)
            return
        full, _, _ = model.apply(params, batch)
        cache = model.init_cache(B, S)
        outs = []
        for t in range(S):
            logits, cache = model.decode(params, cache,
                                         {"tokens": tokens[:, t:t + 1]})
            outs.append(logits)
    inc = jnp.concatenate(outs, axis=1)
    err = jnp.abs(inc - full).max()
    assert float(err) < 2e-3, float(err)
    assert int(cache["pos"]) == S


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-2.7b", "zamba2-1.2b"])
def test_prefill_then_decode_continuation(arch):
    """prefill(tokens[:k]) + decode(tokens[k:]) == forward(tokens)."""
    cfg = get_config(arch).reduced()
    rc = RunConfig(param_dtype="float32", compute_dtype="float32")
    model = build(cfg, rc)
    params = model.init(jax.random.PRNGKey(0))
    B, S, k = 2, 16, 8
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                cfg.vocab_size)
    full, _, _ = model.apply(params, {"tokens": tokens})
    _, cache = model.prefill(params, {"tokens": tokens[:, :k]})
    if "k" in cache:   # grow KV cache to S
        pad = S - k
        cache["k"] = jnp.pad(cache["k"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        cache["v"] = jnp.pad(cache["v"], ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    outs = []
    for t in range(k, S):
        logits, cache = model.decode(params, cache, {"tokens": tokens[:, t:t + 1]})
        outs.append(logits)
    inc = jnp.concatenate(outs, axis=1)
    err = jnp.abs(inc - full[:, k:]).max()
    assert float(err) < 2e-3, float(err)


def test_chunked_attention_matches_dense():
    from repro.models.attention import chunked_attention, full_attention
    key = jax.random.PRNGKey(0)
    B, S, H, hd = 2, 128, 4, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.float32)
               for kk in jax.random.split(key, 3))
    dense = full_attention(q, k, v, causal=True)
    for chunk in (16, 32, 64, 128):
        chunked = chunked_attention(q, k, v, chunk=chunk, causal=True)
        err = jnp.abs(dense - chunked).max()
        assert float(err) < 1e-4, (chunk, float(err))


def _bits(x):
    return np.asarray(jax.lax.bitcast_convert_type(x, jnp.uint16))


def test_in_loop_cast_rounds_as_astype():
    """Decode's integer rounding gives astype's bfloat16 bit for bit."""
    special = np.array([
        0x00000000, 0x80000000, 0x7F800000, 0xFF800000,      # +-0, +-inf
        0x7FC00000, 0xFFC00001, 0x7FFF0000, 0x7FC0FFFF,      # quiet NaNs
        0x7F800001, 0xFF800001, 0x7FA00001, 0x7F80FFFF,      # signalling
        0x7FBFFFFF, 0x7F810000,
        0x00000001, 0x00008000, 0x00018000, 0x007FFFFF,      # subnormals
        0x807FFFFF, 0x00400000, 0x00800000,                  # and min normal
        0x3F808000, 0x3F818000, 0xBF808000, 0x3F808001,      # ties, above one
        0x7F7F8000, 0x7F7FFFFF, 0xFF7FFFFF, 0x7F7E8000,      # largest floats
    ], np.uint32)
    rand = np.random.default_rng(16).integers(0, 2 ** 32, 1 << 16,
                                              dtype=np.uint32)
    x = jnp.asarray(np.concatenate([special, rand]).view(np.float32))
    want = jax.jit(lambda v: v.astype(jnp.bfloat16))(x)
    got = jax.jit(lambda v: transformer._astype_in_place(v, jnp.bfloat16))(x)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_in_loop_cast_passes_compute_dtype_leaves_through():
    """A leaf already in the compute dtype, or kept in float32 by name, is
    returned as it is: no operation is traced for it."""
    tree = {"w": jnp.ones((4, 8), jnp.bfloat16),
            "router": jnp.ones((8, 2), jnp.float32),
            "ids": jnp.ones((3,), jnp.int32)}
    jaxpr = jax.make_jaxpr(
        lambda t: transformer._cast_at_use(t, RunConfig()))(tree)
    assert not jaxpr.eqns


@pytest.mark.parametrize("arch", FAMILY_REPS + ["deepseek-v2-lite"])
def test_decode_casts_at_use_as_a_whole_tree_cast_did(arch):
    """f32 weights cast where each is used give the logits, cache and MoE
    totals of the same step on the whole tree cast to bf16 first."""
    cfg = get_config(arch).reduced()
    rc = RunConfig(param_dtype="float32", compute_dtype="bfloat16")
    params = build(cfg, rc).init(jax.random.PRNGKey(0))
    cast_first = transformer._cast_params(params, rc)
    B = 2
    key = jax.random.PRNGKey(1)
    step = jax.jit(lambda p, c, t, e: transformer.decode_step(
        p, cfg, rc, c, t, embeds=e))
    caches = [transformer.init_cache(cfg, rc, B, 8)] * 2
    for t in range(3):
        k = jax.random.fold_in(key, t)
        if cfg.frontend == "audio":
            tok, emb = None, jax.random.normal(k, (B, 1, cfg.d_model))
        else:
            tok, emb = jax.random.randint(k, (B, 1), 0, cfg.vocab_size), None
        outs = [step(p, c, tok, emb) for p, c in zip((params, cast_first),
                                                      caches)]
        got, want = (jax.tree.map(np.asarray, o) for o in outs)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.dtype == w.dtype
            np.testing.assert_array_equal(np.atleast_1d(g).view(np.uint8),
                                          np.atleast_1d(w).view(np.uint8))
        caches = [o[1] for o in outs]
