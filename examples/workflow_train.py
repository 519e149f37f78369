"""End-to-end driver: distributed LM training orchestrated BY the
KubeAdaptor engine — the paper's control plane running a real ML
pipeline, with real JAX payloads, checkpointing and a fault injection.

The training DAG (namespace-isolated, data flowing through the shared
volume exactly like the paper's PVC):

    data_prep -> train_phase_1 -> ... -> train_phase_P -> eval

Each train phase runs `steps_per_phase` real jitted train steps and
checkpoints; a mid-run pod failure is injected to show the §4.5 fault
tolerance resuming from the checkpoint.

  PYTHONPATH=src python examples/workflow_train.py            # fast (~2 min)
  PYTHONPATH=src python examples/workflow_train.py --arch qwen2-0.5b \\
      --d-model 768 --layers 12 --steps 300                   # ~100M class
"""
import argparse
import dataclasses
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax

from repro.configs import get_config
from repro.core.cluster import Cluster, RUNNING
from repro.core.dag import Task, Workflow
from repro.core.engine import KubeAdaptorEngine
from repro.core.events import EventRegistry
from repro.core.informer import InformerSet
from repro.core.injector import WorkflowInjector
from repro.core.metrics import MetricsCollector
from repro.core.payloads import fn_payload
from repro.core.sim import Sim
from repro.core.volumes import VolumeManager
from repro.checkpoint.checkpointer import Checkpointer
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.optim.adamw import OptConfig, init_state
from repro.runtime.compile_cache import use_compile_cache
from repro.runtime.train import TrainRunConfig, build_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--phases", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override reduced width (0 = tiny test config)")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--inject-failure", action="store_true", default=True)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()
    if args.d_model:
        cfg = dataclasses.replace(cfg, d_model=args.d_model,
                                  d_ff=4 * args.d_model, head_dim=64,
                                  n_heads=args.d_model // 64, n_kv_heads=2)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    print(f"arch={cfg.name}  params~{cfg.param_count() / 1e6:.1f}M  "
          f"steps={args.steps} x {args.phases} phases")

    ckpt_dir = tempfile.mkdtemp(prefix="wf_train_")
    ckpt = Checkpointer(ckpt_dir)
    step_fn, *_ , model = build_train_step(
        cfg, None, B=args.batch, S=args.seq,
        trc=TrainRunConfig(opt=OptConfig(lr=3e-4, warmup_steps=20,
                                         total_steps=args.steps)))
    data = iter(SyntheticLM(DataConfig(args.batch, args.seq, cfg.vocab_size)))
    losses = []

    def data_prep():
        # warm the pipeline + write the tokenizer/dataset manifest
        next(data)
        return {"dataset": "synthetic-zipf", "vocab": cfg.vocab_size}

    def make_phase(phase_idx, n_steps):
        def train_phase():
            latest = ckpt.latest_step()
            sds = jax.eval_shape(lambda: init_state(
                model.init(jax.random.PRNGKey(0))))
            if latest is None:
                state = init_state(model.init(jax.random.PRNGKey(0)))
            else:
                state = ckpt.restore(sds)
            start = int(state.step)
            for _ in range(start, min(start + n_steps, args.steps)):
                state, m = step_fn(state, next(data))
                losses.append(float(m["loss"]))
            ckpt.save(state, int(state.step), blocking=True)
            return {"phase": phase_idx, "step": int(state.step),
                    "loss": losses[-1] if losses else None}
        return train_phase

    def evaluate():
        sds = jax.eval_shape(lambda: init_state(model.init(jax.random.PRNGKey(0))))
        state = ckpt.restore(sds)
        batch = next(data)
        loss = float(model.loss(state.params, jax.tree.map(jax.numpy.asarray, batch)))
        return {"eval_loss": loss, "step": int(state.step)}

    per_phase = args.steps // args.phases
    tasks = {"data_prep": Task(id="data_prep", outputs=["phase_1"],
                               payload=fn_payload(data_prep), duration_s=1.0)}
    prev = "data_prep"
    for i in range(1, args.phases + 1):
        tid = f"phase_{i}"
        nxt = f"phase_{i + 1}" if i < args.phases else "eval"
        tasks[tid] = Task(id=tid, inputs=[prev], outputs=[nxt],
                          payload=fn_payload(make_phase(i, per_phase)),
                          duration_s=5.0)
        prev = tid
    tasks["eval"] = Task(id="eval", inputs=[prev], outputs=[],
                         payload=fn_payload(evaluate), duration_s=2.0)
    wf = Workflow("lmtrain", tasks)

    sim = Sim()
    cluster = Cluster(sim, payload_mode="real", seed=0)
    informers = InformerSet(sim, cluster)
    events = EventRegistry(sim)
    volumes = VolumeManager(sim, cluster)
    metrics = MetricsCollector(sim, cluster)
    engine = KubeAdaptorEngine(sim, cluster, informers, events, volumes, metrics)
    injector = WorkflowInjector(sim, engine.submit)
    engine.on_workflow_done = injector.request_next
    injector.load([wf.with_instance(0)])
    injector.start()

    if args.inject_failure:
        # kill the phase-2 pod mid-run: fault tolerance restarts it and the
        # payload resumes from the checkpoint (no lost progress)
        def nuke():
            for p in cluster.list_pods():
                if p.task_id == "phase_2" and p.phase == RUNNING:
                    print("!! injecting pod failure on phase_2")
                    cluster.fail_pod(p.namespace, p.name)
                    return
            sim.after(1.0, nuke)
        sim.after(8.0, nuke)

    sim.run(until=1e9)
    rec = metrics.wf_record(wf.with_instance(0))
    vol_summary = {}
    print(f"\nworkflow lifecycle (virtual): {rec.lifecycle:.1f}s  "
          f"retries={rec.retries}")
    print(f"order consistent: {metrics.order_consistent(wf.with_instance(0))}")
    print(f"steps completed: {ckpt.latest_step()}  "
          f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "training did not descend"
    print("OK")


if __name__ == "__main__":
    main()
