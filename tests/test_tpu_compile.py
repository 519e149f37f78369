"""Compile the chip smoke's programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with jax, compiles for a
``v5e:2x2`` topology that is described, not attached. That refuses what
interpret mode cannot — unsupported kernel ops, VMEM overuse, programs
that do not fit the chip's HBM. The topology is described inside a
fixture (never at import), so every xdist worker collects the same tests
and only the worker given this file loads the TPU library.
"""
import importlib.util
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import RunConfig
from repro.runtime.serve import build_decode_step, build_prefill_step
from repro.runtime.train import TrainRunConfig, build_train_step

HBM_BYTES = 15.75e9           # what the v5e compiler lets one program use
REPO = Path(__file__).resolve().parent.parent


def _smoke():
    path = REPO / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


SMOKE = _smoke()


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    # a TPU compile written here could never be read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _fits(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 1e9:.2f} GB of {HBM_BYTES / 1e9} GB"


def test_flash_attention_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-0.5b")
    x = jax.ShapeDtypeStruct((4, 1024, cfg.n_heads, cfg.resolved_head_dim),
                             jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, causal=True)
    ).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-2.7b")
    h = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    b, s, p, n = 2, 1024, cfg.ssm_head_dim, cfg.ssm_state
    f32 = jnp.float32
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in [((b, s, h, p), jnp.bfloat16), ((b, s, h), f32),
                              ((h,), f32), ((b, s, n), f32), ((b, s, n), f32)]]
    compiled = jax.jit(
        lambda *a: ssd_scan(*a, chunk=cfg.ssm_chunk)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B,accum", [(SMOKE.TRAIN_B, 1),     # ml_pipeline
                                     (SMOKE.SHARDED_B, 4)])  # --chips 4 ref
def test_train_step_fits_one_chip_at_smoke_size(one_chip, B, accum):
    cfg = get_config(SMOKE.ARCH)
    step, state, batch, *_ = build_train_step(
        cfg, None, B=B, S=SMOKE.TRAIN_S,
        rc=RunConfig(remat=True, remat_policy="full"),
        trc=TrainRunConfig(grad_accum=accum))
    compiled = step.lower(_on(one_chip, state), _on(one_chip, batch)).compile()
    _fits(compiled)
    # below attn_dense_max qwen2 attends densely: no attention kernel
    assert "splash_mha" not in compiled.as_text()


@pytest.mark.parametrize("stage", ["prefill", "decode"])
def test_serve_step_fits_one_chip_at_smoke_size(one_chip, stage):
    cfg = get_config(SMOKE.ARCH)
    if stage == "prefill":
        fn, params, batch, *_ = build_prefill_step(
            cfg, None, B=SMOKE.PREFILL_B, S=SMOKE.PREFILL_S)
        args = (params, batch)
    else:
        fn, params, cache, batch, *_ = build_decode_step(
            cfg, ShapeConfig("decode", "decode", SMOKE.CACHE_LEN,
                             SMOKE.PREFILL_B), None)
        args = (params, cache, batch)
    _fits(fn.lower(*(_on(one_chip, a) for a in args)).compile())


def test_sharded_train_step_compiles_on_2x2(topo):
    cfg = get_config(SMOKE.ARCH)
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    step, state, batch, *_ = build_train_step(
        cfg, mesh, B=SMOKE.SHARDED_B, S=SMOKE.TRAIN_S,
        rc=RunConfig(remat=True, remat_policy="full"))
    compiled = step.lower(state, batch).compile()
    _fits(compiled)                           # per-device bytes
    text = compiled.as_text()
    assert "all-reduce" in text and "all-gather" in text


def _bench_mla_moe():
    """The dsv2lite.train8k cell's model and program configuration."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    from bench.kinds.ml_pipeline_mla_moe import arch_config
    m = json.loads((REPO / "bench/configs/mlpipe-deepseek-v2-lite.json")
                   .read_text())
    run = m["run"]
    rc = RunConfig(param_dtype=run["param_dtype"],
                   compute_dtype=run["compute_dtype"],
                   attn_chunk=run["attn_chunk"],
                   attn_dense_max=run["attn_dense_max"],
                   moe_group=run["moe_group"])
    return arch_config(m, m["name"]), rc


def _entry_bf16_casts(text):
    """Shapes of the bf16 arrays the entry computation's converts and
    fusions make."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    return {tuple(map(int, dims.split(","))) for dims in re.findall(
        r"= bf16\[([\d,]+)\]\S* (?:convert|fusion)\(", entry)}


@pytest.mark.parametrize("arch,B,cache", [("qwen2-0.5b", 8, 1024),
                                          ("mlpipe-deepseek-v2-lite", 4, 8192)])
def test_decode_casts_weights_inside_the_layer_scan(one_chip, arch, B, cache):
    """The f32 weight matrices reach the decode program's layer loop as
    they are stored: the entry computation casts no layer stack (nor the
    embedding or head) to bf16. A stack of one layer is left out: XLA
    drops its one-trip loop, so its cast is that layer's."""
    if arch == "qwen2-0.5b":
        cfg, rc = get_config(arch), RunConfig()
    else:
        cfg, rc = _bench_mla_moe()
    fn, params, kv, batch, *_ = build_decode_step(
        cfg, ShapeConfig("decode", "decode", cache, B), None, rc=rc,
        with_stats=cfg.n_experts > 0)
    compiled = fn.lower(*(_on(one_chip, a) for a in (params, kv, batch))
                        ).compile()
    _fits(compiled)
    weights = {w.shape for w in jax.tree.leaves(params["blocks"])
               if w.ndim > 2 and w.shape[0] > 1 and w.dtype == jnp.float32}
    weights |= {params[k].shape for k in ("embed", "head") if k in params}
    cast = _entry_bf16_casts(compiled.as_text())
    assert len(weights) >= 5 and not weights & cast, sorted(weights & cast)


def test_mla_layer_attention_lowers_to_the_splash_kernel(one_chip):
    """One DeepSeek-V2-Lite MLA layer, forward and backward under full
    remat, at dsv2lite.train8k's B2 S8192: attention is the splash
    forward, dq and dkv kernels, no key-chunk loop is left, and it
    fits."""
    from repro.models import mla as mla_lib
    cfg, rc = _bench_mla_moe()
    params = jax.eval_shape(
        lambda: mla_lib.init_mla(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    x = jax.ShapeDtypeStruct((2, 8192, cfg.d_model), jnp.bfloat16,
                             sharding=one_chip)

    @jax.checkpoint
    def layer(p, x):
        return mla_lib.apply_mla(p, x, cfg, rc, jnp.arange(x.shape[1]))[0]
    compiled = jax.jit(jax.grad(
        lambda p, x: jnp.sum(layer(p, x).astype(jnp.float32)), (0, 1))
    ).lower(_on(one_chip, params), x).compile()
    _fits(compiled)
    text = compiled.as_text()
    for phase in ("fwd", "dq", "dkv"):
        assert f"splash_mha_{phase}" in text, phase
    assert " while(" not in text
