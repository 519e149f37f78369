"""Per-architecture smoke tests: reduced config, one forward + one train
step on CPU, asserting output shapes and no NaNs (assignment req (f))."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import REGISTRY, get_config, list_configs
from repro.models import RunConfig, build
from repro.optim.adamw import OptConfig
from repro.runtime.train import TrainRunConfig, build_train_step

ARCHS = list_configs()


def _batch(cfg, B=2, S=32, seed=1):
    key = jax.random.PRNGKey(seed)
    batch = {}
    if cfg.frontend == "audio":
        batch["embeds"] = jax.random.normal(key, (B, S, cfg.d_model), jnp.bfloat16)
    else:
        batch["tokens"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
        if cfg.frontend == "vision":
            batch["img_embeds"] = jax.random.normal(
                key, (B, cfg.n_img_tokens, cfg.d_model), jnp.bfloat16)
    batch["labels"] = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return batch


def test_all_ten_archs_registered():
    # the ten assigned architectures and deepseek-v2-lite
    assert len(ARCHS) == 11
    families = {REGISTRY[a].family for a in ARCHS}
    assert families == {"dense", "moe", "ssm", "hybrid", "audio", "vlm"}


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    model = build(cfg, RunConfig())
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits, aux, _ = model.apply(params, batch)
    B, S = 2, 32
    assert logits.shape == (B, S, cfg.vocab_padded)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    assert bool(jnp.isfinite(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_descends_and_finite(arch):
    cfg = get_config(arch).reduced()
    step, state_sds, _, _, _, model = build_train_step(
        cfg, None, B=2, S=32,
        trc=TrainRunConfig(opt=OptConfig(lr=1e-3, warmup_steps=1,
                                         total_steps=10)))
    from repro.optim.adamw import init_state
    state = init_state(model.init(jax.random.PRNGKey(0)))
    batch = _batch(cfg)
    state, m1 = step(state, batch)
    state, m2 = step(state, batch)   # same batch twice -> loss must drop
    assert bool(jnp.isfinite(m1["loss"])) and bool(jnp.isfinite(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])
    assert int(state.step) == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_grad_accumulation_matches_full_batch(arch):
    """grad_accum=2 over the same data == single big batch (to fp tolerance)."""
    cfg = get_config(arch).reduced()
    trc1 = TrainRunConfig(opt=OptConfig(lr=1e-3), grad_accum=1)
    trc2 = TrainRunConfig(opt=OptConfig(lr=1e-3), grad_accum=2)
    step1, *_, model = build_train_step(cfg, None, B=4, S=16, trc=trc1)
    step2, *_ = build_train_step(cfg, None, B=4, S=16, trc=trc2)
    from repro.optim.adamw import init_state
    batch = _batch(cfg, B=4, S=16)
    # NOTE: the step donates its input state — build a fresh one per call
    _, ma = step1(init_state(model.init(jax.random.PRNGKey(0))), batch)
    _, mb = step2(init_state(model.init(jax.random.PRNGKey(0))), batch)
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=2e-2)


def test_param_counts_match_published_sizes():
    # analytic totals should be in the right ballpark of the model names
    expect = {
        "mamba2-2.7b": (2.4e9, 3.1e9),
        "zamba2-1.2b": (1.0e9, 1.4e9),
        "llama4-scout-17b-a16e": (95e9, 115e9),   # 109B total published
        "qwen2-moe-a2.7b": (13e9, 15.5e9),        # 14.3B total published
        "qwen2-1.5b": (1.3e9, 1.8e9),
        "gemma-7b": (7.8e9, 9.5e9),
        "deepseek-67b": (64e9, 70e9),
        "qwen2-0.5b": (0.4e9, 0.65e9),
        "musicgen-medium": (1.3e9, 2.1e9),
        "llama-3.2-vision-11b": (9e9, 11e9),      # minus the vision stub
        "deepseek-v2-lite": (15.2e9, 16.2e9),     # 15.7B total published
    }
    for arch, (lo, hi) in expect.items():
        n = get_config(arch).param_count()
        assert lo <= n <= hi, (arch, n)


def test_moe_active_params_below_total():
    for arch in ("llama4-scout-17b-a16e", "qwen2-moe-a2.7b", "deepseek-v2-lite"):
        cfg = get_config(arch)
        assert cfg.active_param_count() < 0.5 * cfg.param_count()
