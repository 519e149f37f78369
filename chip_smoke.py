#!/usr/bin/env python3
"""Chip smoke test: the workflow engine's real-payload path on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: sharded train step only

One process, in phases; each phase prints its facts on lines of its own
and raises on any failed check. With no arguments:

  gate            the first JAX device must be a TPU (there is no CPU
                  fallback); prints its kind, count, jax/libtpu versions
  paper_workflow  the paper's Montage DAG through run_experiment(
                  payload_mode="real"); every task is a device matmul
  ml_pipeline     data_prep -> train_1 -> train_2 -> eval -> prefill ->
                  decode through ControlPlane.add_stream, on qwen2-0.5b at
                  its published widths; train_2 resumes from train_1's
                  checkpoint; decode is checked against a longer prefill
  kernels         Pallas flash attention and SSD scan, compiled for the
                  chip, against the jnp production paths

With --chips 4 it runs only the sharded qwen2-0.5b train step on a
(data=2, model=2) mesh and compares its losses with the one-chip step on
the same batches.

The last line of stdout is one JSON object naming the device. The
compile cache is placed by repro.runtime.compile_cache.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
CKPT_DIR = ROOT / ".smoke_ckpt"      # train_1 -> train_2 checkpoint (~6 GB)

ARCH = "qwen2-0.5b"                  # configs/qwen2_0p5b.py, never .reduced()
TRAIN_B, TRAIN_S = 4, 1024
TRAIN_STEPS = (3, 3)                 # steps in train_1, train_2
LR = 3e-4
PREFILL_B, PREFILL_S = 8, 512
DECODE_STEPS, CACHE_LEN = 16, 1024
SHARDED_B, SHARDED_STEPS = 8, 3
MATMUL_N, MATMUL_ITERS = 2048, 8     # paper-workflow task payload

# decode logits vs a prefill over the same tokens, both bf16 compute:
# max|a - b| <= LOGIT_TOL * max|b|
LOGIT_TOL = 5e-2
# sharded vs one-chip train loss, per step: |a - b| <= LOSS_TOL * |b|
LOSS_TOL = 2e-3
# kernels: the bf16 tolerances of tests/test_kernels.py (atol = rtol)
FLASH_TOL, SSD_TOL = 2e-2, 5e-2


def say(phase: str, **facts) -> None:
    print(phase + " " + " ".join(f"{k}={v}" for k, v in facts.items()),
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _version(dist: str) -> str:
    from importlib import metadata
    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return "not-installed"


def device_gate(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found "
                 f"platform={d.platform!r} kind={d.device_kind!r} "
                 f"count={len(devs)}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    say("gate", platform=d.platform, device_kind=repr(d.device_kind),
        count=len(devs), jax=jax.__version__, libtpu=_version("libtpu"))
    return d


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


class Executables:
    """AOT-compiled steps, compiled on first use; records compile seconds."""

    def __init__(self):
        self._exe = {}

    def get(self, name, jitted, *args):
        """Returns (executable, compile seconds spent by this call)."""
        if name in self._exe:
            return self._exe[name], 0.0
        t0 = time.perf_counter()
        exe = jitted.lower(*args).compile()
        self._exe[name] = exe
        return exe, time.perf_counter() - t0


def _all_succeeded(res, wf) -> None:
    from repro.core.cluster import SUCCEEDED
    ns = wf.namespace()
    done = {p.task_id for p in res.cluster.pod_log
            if p.namespace == ns and p.phase == SUCCEEDED}
    missing = sorted(set(wf.tasks) - done)
    check(not missing, f"{wf.name}: tasks not SUCCEEDED: {missing}")
    check(res.metrics.order_consistent(wf),
          f"{wf.name}: start order is not consistent with the DAG")


# ---------------------------------------------------------------------------
def phase_paper_workflow() -> None:
    from repro.configs.workflows import get_workflow_spec
    from repro.core.dag import make_workflow
    from repro.core.payloads import matmul_payload
    from repro.core.runner import run_experiment

    wf = make_workflow("montage", get_workflow_spec("montage"))
    payload = matmul_payload(MATMUL_N, MATMUL_ITERS)
    t0 = time.perf_counter()
    payload(None, None)                      # compile outside the pods
    warm_s = time.perf_counter() - t0
    for t in wf.tasks.values():
        t.payload = payload
    t0 = time.perf_counter()
    res = run_experiment("kubeadaptor", wf, payload_mode="real")
    wall_s = time.perf_counter() - t0
    inst = wf.with_instance(0)
    _all_succeeded(res, inst)
    runs = [p.finished - p.started for p in res.cluster.pod_log
            if p.namespace == inst.namespace()]
    say("paper_workflow", workflow="montage", tasks=len(wf.tasks),
        all_succeeded=True, order_consistent=True,
        payload=f"matmul_n{MATMUL_N}x{MATMUL_ITERS}",
        compile_s=warm_s, wall_s=wall_s, pod_run_s_min=min(runs),
        pod_run_s_max=max(runs),
        lifecycle_virtual_s=res.metrics.wf_record(inst).lifecycle)


# ---------------------------------------------------------------------------
def phase_ml_pipeline() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.dag import Task, Workflow
    from repro.core.payloads import fn_payload
    from repro.core.runner import ControlPlane
    from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
    from repro.models import RunConfig
    from repro.optim.adamw import OptConfig
    from repro.runtime.serve import build_decode_step, build_prefill_step
    from repro.runtime.train import (TrainRunConfig, build_train_step,
                                     init_sharded_state)

    cfg = get_config(ARCH)
    train_step, state_sds, batch_sds, _, _, model = build_train_step(
        cfg, None, B=TRAIN_B, S=TRAIN_S,
        rc=RunConfig(remat=True, remat_policy="full"),
        trc=TrainRunConfig(opt=OptConfig(lr=LR, warmup_steps=1,
                                         total_steps=sum(TRAIN_STEPS))))
    prefill, params_sds, prefill_sds, _, _ = build_prefill_step(
        cfg, None, B=PREFILL_B, S=PREFILL_S)
    decode, _, cache_sds, decode_sds, _, _ = build_decode_step(
        cfg, ShapeConfig("smoke_decode", "decode", CACHE_LEN, PREFILL_B), None)
    exes = Executables()
    ckpt = Checkpointer(CKPT_DIR, keep=1)
    ctx: dict = {}
    losses: list = []

    def pod(name, compile_s, run_s, **facts):
        say("pod", name=name, compile_s=compile_s, run_s=run_s,
            peak_bytes_in_use=peak_bytes(), **facts)

    def data_prep():
        t0 = time.perf_counter()
        ctx["train"] = shard_batch(next(SyntheticLM(
            DataConfig(TRAIN_B, TRAIN_S, cfg.vocab_size, seed=0))), None)
        ctx["eval"] = shard_batch(next(SyntheticLM(
            DataConfig(TRAIN_B, TRAIN_S, cfg.vocab_size, seed=1))), None)
        ctx["prompts"] = jnp.asarray(next(SyntheticLM(
            DataConfig(PREFILL_B, PREFILL_S, cfg.vocab_size, seed=2)))["tokens"])
        jax.block_until_ready(ctx["prompts"])
        pod("data_prep", 0.0, time.perf_counter() - t0)
        return {"tokens": TRAIN_B * TRAIN_S * 2 + PREFILL_B * PREFILL_S}

    def train_phase(name, n_steps, last):
        def run():
            exe, compile_s = exes.get("train", train_step, state_sds, batch_sds)
            t0 = time.perf_counter()
            if ckpt.latest_step() is None:
                state = init_sharded_state(model, None, None, seed=0)
            else:
                state = ckpt.restore(state_sds)
            start = int(state.step)
            setup_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            step_losses = []
            for _ in range(n_steps):
                state, m = exe(state, ctx["train"])
                step_losses.append(m["loss"])
            jax.block_until_ready((state, step_losses))
            run_s = time.perf_counter() - t0
            step_losses = [float(x) for x in step_losses]
            losses.extend(step_losses)
            t0 = time.perf_counter()
            if last:                         # the serving stages use these
                ctx["params"] = state.params
            else:                            # the next phase resumes here
                ckpt.save(state, int(state.step), blocking=True)
            save_s = time.perf_counter() - t0
            del state
            pod(name, compile_s, run_s, start_step=start,
                setup_s=setup_s, ckpt_save_s=save_s, losses=step_losses)
            return {"step": start + n_steps, "loss": step_losses[-1]}
        return run

    def evaluate():
        exe, compile_s = exes.get("eval", jax.jit(model.loss), params_sds,
                                  batch_sds)
        t0 = time.perf_counter()
        loss = exe(ctx["params"], ctx["eval"])
        loss.block_until_ready()
        run_s = time.perf_counter() - t0
        ctx["eval_loss"] = float(loss)
        pod("eval", compile_s, run_s, eval_loss=ctx["eval_loss"])
        return {"eval_loss": loss}

    def do_prefill():
        exe, compile_s = exes.get("prefill", prefill, params_sds, prefill_sds)
        t0 = time.perf_counter()
        logits, cache = exe(ctx["params"], {"tokens": ctx["prompts"]})
        pad = CACHE_LEN - PREFILL_S
        for k in ("k", "v"):                 # (L, B, S, K, hd) -> CACHE_LEN
            cache[k] = jnp.pad(cache[k], ((0, 0), (0, 0), (0, pad),
                                          (0, 0), (0, 0)))
        ctx["cache"] = cache
        ctx["first"] = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        jax.block_until_ready((cache, ctx["first"]))
        pod("prefill", compile_s, time.perf_counter() - t0,
            tokens=PREFILL_B * PREFILL_S)
        return {"cache_pos": cache["pos"]}

    def do_decode():
        exe, compile_s = exes.get("decode", decode, params_sds, cache_sds,
                                  decode_sds)
        t0 = time.perf_counter()
        cache, tok, fed = ctx.pop("cache"), ctx["first"], []
        for _ in range(DECODE_STEPS):
            fed.append(tok)
            logits, cache = exe(ctx["params"], cache, {"tokens": tok})
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        jax.block_until_ready((logits, cache))
        run_s = time.perf_counter() - t0
        ctx["fed"], ctx["last_logits"] = jnp.concatenate(fed, 1), logits
        pod("decode", compile_s, run_s, steps=DECODE_STEPS,
            tokens_per_s=PREFILL_B * DECODE_STEPS / run_s,
            cache_pos=int(cache["pos"]))
        return {"generated": ctx["fed"]}

    stages = [("data_prep", data_prep),
              ("train_1", train_phase("train_1", TRAIN_STEPS[0], False)),
              ("train_2", train_phase("train_2", TRAIN_STEPS[1], True)),
              ("eval", evaluate), ("prefill", do_prefill),
              ("decode", do_decode)]
    tasks = {}
    for i, (tid, fn) in enumerate(stages):
        tasks[tid] = Task(
            id=tid, inputs=[stages[i - 1][0]] if i else [],
            outputs=[stages[i + 1][0]] if i + 1 < len(stages) else [],
            payload=fn_payload(fn))
    wf = Workflow("ml_pipeline", tasks)

    shutil.rmtree(CKPT_DIR, ignore_errors=True)
    try:
        plane = ControlPlane("kubeadaptor", payload_mode="real")
        plane.add_stream(wf)
        t0 = time.perf_counter()
        res = plane.run()
        wall_s = time.perf_counter() - t0
    finally:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
    _all_succeeded(res, wf.with_instance(0))

    check(len(losses) == sum(TRAIN_STEPS), f"losses: {losses}")
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"train loss did not fall: {losses}")
    check(math.isfinite(ctx["eval_loss"]), f"eval loss {ctx['eval_loss']}")

    # decode at position P against a prefill over the same P+1 tokens
    long_s = PREFILL_S + DECODE_STEPS
    prefill_long, _, long_sds, _, _ = build_prefill_step(
        cfg, None, B=PREFILL_B, S=long_s)
    exe, compile_s = exes.get("prefill_long", prefill_long, params_sds,
                              long_sds)
    tokens = jnp.concatenate([ctx["prompts"], ctx["fed"]], 1)
    ref, _ = exe(ctx["params"], {"tokens": tokens})
    got = np.asarray(ctx["last_logits"], np.float32)
    ref = np.asarray(ref, np.float32)
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    check(np.isfinite(got).all() and got.shape == ref.shape,
          f"decode logits {got.shape} vs {ref.shape}")
    check(err <= LOGIT_TOL * scale,
          f"decode vs prefill: max|d|={err} > {LOGIT_TOL} * {scale}")
    say("ml_pipeline", arch=ARCH, params=cfg.param_count(),
        all_succeeded=True, order_consistent=True, wall_s=wall_s,
        loss_first=losses[0], loss_last=losses[-1],
        eval_loss=ctx["eval_loss"])
    say("check", name="decode_vs_prefill", position=long_s - 1,
        max_abs_diff=err, max_abs_ref=scale, tol=f"{LOGIT_TOL}*max_abs_ref",
        argmax_agree=agree, compile_s=compile_s)


# ---------------------------------------------------------------------------
def _max_rel_violation(got, ref, tol):
    """max over elements of |got-ref| / (tol + tol*|ref|); <= 1 passes."""
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape and np.isfinite(got).all(),
          f"shape {got.shape} vs {ref.shape} or non-finite output")
    return float((np.abs(got - ref) / (tol + tol * np.abs(ref))).max())


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ssd_scan import ssd_scan
    from repro.models.attention import chunked_attention
    from repro.models.ssm import ssd_chunked

    q_cfg, m_cfg = get_config(ARCH), get_config("mamba2-2.7b")
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    B, S, H, hd = 4, 1024, q_cfg.n_heads, q_cfg.resolved_head_dim
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), jnp.bfloat16)
               for kk in ks[:3])
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t0 = time.perf_counter()
    out = fa(q, k, v).block_until_ready()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fa(q, k, v).block_until_ready()
    run_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        ref = chunked_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                                chunk=256, causal=True)
    viol = _max_rel_violation(out, ref, FLASH_TOL)
    check(viol <= 1.0, f"flash_attention vs chunked_attention: {viol}")
    say("kernel", name="flash_attention", shape=f"B{B}xS{S}xH{H}xhd{hd}",
        dtype="bfloat16", first_call_s=first_s, run_s=run_s,
        ref="chunked_attention_f32", tol=FLASH_TOL, max_err_over_tol=viol)

    b, s = 2, 1024
    h = m_cfg.ssm_expand * m_cfg.d_model // m_cfg.ssm_head_dim
    p, n, chunk = m_cfg.ssm_head_dim, m_cfg.ssm_state, m_cfg.ssm_chunk
    x = jax.random.normal(ks[3], (b, s, h, p), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[5], (h,)) * 0.3)
    Bm = jax.random.normal(ks[6], (b, s, n)) * 0.5
    Cm = jax.random.normal(ks[7], (b, s, n)) * 0.5
    ssd = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))
    t0 = time.perf_counter()
    y, st = jax.block_until_ready(ssd(x, dt, A, Bm, Cm))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    y, st = jax.block_until_ready(ssd(x, dt, A, Bm, Cm))
    run_s = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        y_ref, st_ref = ssd_chunked(x.astype(jnp.float32), dt, A, Bm, Cm,
                                    chunk)
    viol = max(_max_rel_violation(y, y_ref, SSD_TOL),
               _max_rel_violation(st, st_ref, SSD_TOL))
    check(viol <= 1.0, f"ssd_scan vs ssd_chunked: {viol}")
    say("kernel", name="ssd_scan", shape=f"b{b}xs{s}xH{h}xP{p}xN{n}",
        chunk=chunk, dtype="bfloat16", first_call_s=first_s, run_s=run_s,
        ref="ssd_chunked_f32", tol=SSD_TOL, max_err_over_tol=viol)


# ---------------------------------------------------------------------------
def phase_sharded_train() -> None:
    import jax

    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM, shard_batch
    from repro.launch.mesh import make_mesh
    from repro.models import RunConfig
    from repro.optim.adamw import OptConfig
    from repro.parallel.sharding import ShardingPolicy, batch_specs
    from repro.runtime.train import (TrainRunConfig, build_train_step,
                                     init_sharded_state)

    cfg = get_config(ARCH)
    rc = RunConfig(remat=True, remat_policy="full")
    opt = OptConfig(lr=LR, warmup_steps=1, total_steps=SHARDED_STEPS)
    data = SyntheticLM(DataConfig(SHARDED_B, TRAIN_S, cfg.vocab_size, seed=0))
    batches = [next(data) for _ in range(SHARDED_STEPS)]
    devices = set(jax.devices()[:4])

    def run(name, mesh, trc):
        step, state_sds, batch_sds, st_sh, _, model = build_train_step(
            cfg, mesh, B=SHARDED_B, S=TRAIN_S, rc=rc, trc=trc)
        t0 = time.perf_counter()
        exe = step.lower(state_sds, batch_sds).compile()
        compile_s = time.perf_counter() - t0
        state = init_sharded_state(model, mesh, st_sh, seed=0)
        if mesh is not None:
            for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
                held = {sh.device for sh in leaf.addressable_shards}
                check(held == devices,
                      f"{jax.tree_util.keystr(path)} is held by "
                      f"{sorted(d.id for d in held)}, not all 4 devices")
        specs = (batch_specs(batch_sds, mesh, ShardingPolicy())
                 if mesh is not None else None)
        losses = []
        t0 = time.perf_counter()
        for b in batches:
            state, m = exe(state, shard_batch(b, mesh, specs))
            losses.append(m["loss"])
        jax.block_until_ready((state, losses))
        run_s = time.perf_counter() - t0
        losses = [float(x) for x in losses]
        say("sharded_train", name=name, compile_s=compile_s, run_s=run_s,
            peak_bytes_in_use_dev0=peak_bytes(), losses=losses)
        return losses

    mesh = make_mesh((2, 2), ("data", "model"))
    sharded = run("mesh_data2_model2", mesh, TrainRunConfig(opt=opt))
    # B=8 in one pass needs 15.1 GB next to the 5.9 GB f32 AdamW state on
    # a 16 GB chip, so the reference accumulates four microbatches of 2:
    # the same mean loss and mean gradient over the same 8 sequences
    single = run("one_chip_accum4", None,
                 TrainRunConfig(opt=opt, grad_accum=4))
    worst = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
    check(all(math.isfinite(x) for x in sharded + single),
          f"non-finite loss: {sharded} {single}")
    check(worst <= LOSS_TOL,
          f"sharded vs one-chip loss: rel diff {worst} > {LOSS_TOL}")
    say("sharded_train", arch=ARCH, batch=SHARDED_B, seq=TRAIN_S,
        steps=SHARDED_STEPS, all_leaves_on_4_devices=True,
        max_rel_loss_diff=worst, tol=LOSS_TOL)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train-step phase")
    args = ap.parse_args()

    dev = device_gate(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import use_compile_cache
    say("compile_cache", dir=use_compile_cache())

    if args.chips == 4:
        phase_sharded_train()
    else:
        phase_paper_workflow()
        phase_ml_pipeline()
        phase_kernels()

    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
