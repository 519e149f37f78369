"""Driver for ML pipeline workflows: the program's own train, eval,
prefill and decode steps as the task payloads of one workflow DAG.

The traffic file lists the stages of a chain, each with its shapes:

    data_prep   draws the workflow's token streams from the seed
    train       ``steps`` AdamW steps of the program's train step on the
                resident train state, which carries over from workflow
                to workflow (continual fine-tuning)
    eval        the program's loss on held-out tokens, trained weights
    prefill     the program's prefill of ``batch`` prompts on the
                deployed (seed) weights, its cache padded to ``cache``
    decode      ``steps`` greedy decode steps through that cache

Every stage is a ``fn_payload`` over the program's step, run by the
``ControlPlane``. The steps are compiled ahead of time under names of
the benchmark's own (``bench_*``), so the trace reduction finds them.

Correct means: every completed workflow's tasks SUCCEEDED in DAG order;
the train step's first three losses, its first clipped gradient and its
parameter change after three steps agree with the float32 reference
(``bench/ref/qwen2.py``); and every served token of a sample of the
window's requests is within the limit of the reference's best logit.
"""
from __future__ import annotations

import random
import time
from collections import defaultdict
from typing import Dict, List

from bench.harness import (Check, WindowClosed, engine_faults,
                           finished_workflows, run_until_closed)
from bench.ref import qwen2 as ref
from bench.ref import weights as W

STAGE_KINDS = ("data_prep", "train", "eval", "prefill", "decode")


def arch_config(m: dict, name: str):
    """The program's ArchConfig for the configuration's published keys."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        qkv_bias=m["qkv_bias"], tie_embeddings=m["tie_word_embeddings"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"])


def leaf_norms(tree) -> Dict[str, float]:
    import jax
    return {jax.tree_util.keystr(p): float(v)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def worst_norm_gap(got: Dict[str, float], want: Dict[str, float],
                   keep) -> float:
    """max over kept leaves of |got - want| / max(want, median want)."""
    import statistics
    med = statistics.median(want[k] for k in keep)
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in keep)


class Driver:
    def __init__(self, cell, seed: int, spans):
        self.cell = cell
        self.cfg = cell.config
        self.m = self.cfg["model"]
        self.traffic = cell.traffic
        self.stages = self.traffic["stages"]
        for s in self.stages:
            if s["kind"] not in STAGE_KINDS:
                raise ValueError(f"unknown stage kind {s['kind']!r}")
        self.seed = seed
        self.spans = spans
        self.deadline = float("inf")
        self.recording = False
        self.stamps: List[tuple] = []        # (ns, stage kind, w0, w1)
        self.ctx: Dict[str, dict] = defaultdict(dict)
        self.served: List[tuple] = []         # (workflow, device tokens)
        self.eval_losses: List = []
        self.n_workflows = 0
        self.first: Dict[str, object] = {}
        self.plane = None
        self.phases: Dict[str, float] = {}
        self._t_phase = time.perf_counter()
        self._want = None                      # the reference's readings
        self.readings: Dict[str, float] = {}   # read but not compared

    def _phase(self, name):
        """Record the seconds since the last phase mark under ``name``."""
        now = time.perf_counter()
        self.phases[name] = now - self._t_phase
        self._t_phase = now

    def _stage(self, kind):
        return next((s for s in self.stages if s["kind"] == kind), None)

    # -- set-up ----------------------------------------------------------------
    def setup(self):
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ShapeConfig
        from repro.models import RunConfig
        from repro.optim.adamw import OptConfig
        from repro.runtime.serve import build_decode_step, build_prefill_step
        from repro.runtime.train import TrainRunConfig, build_train_step

        self._phase("imports")
        cfg, m = self.cfg, self.m
        arch = arch_config(m, cfg["name"])
        run = cfg["run"]
        rc = RunConfig(param_dtype=run["param_dtype"],
                       compute_dtype=run["compute_dtype"],
                       remat=run["remat"] != "none",
                       remat_policy=run["remat"])
        o = cfg["optimizer"]
        self.opt = OptConfig(lr=o["lr"], warmup_steps=o["warmup_steps"],
                             total_steps=o["total_steps"],
                             min_lr_ratio=o["min_lr_ratio"], b1=o["b1"],
                             b2=o["b2"], eps=o["eps"],
                             weight_decay=o["weight_decay"],
                             clip_norm=o["clip_norm"])
        # keys are arguments, never constants folded into a program:
        # a constant would make every seed compile its own programs
        self.key = W.seed_key(self.seed)
        self.exe: Dict[str, object] = {}
        self.mem: Dict[str, int] = {}

        def aot(name, fn, *args, donate=()):
            with self.spans("compile"):
                c = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
            ma = c.memory_analysis()
            if ma is not None:
                self.mem[name] = int(ma.temp_size_in_bytes
                                     + ma.output_size_in_bytes
                                     - ma.alias_size_in_bytes)
            self.exe[name] = c
            return c

        tr, ev = self._stage("train"), self._stage("eval")
        pf, dc = self._stage("prefill"), self._stage("decode")
        params_sds = None
        if tr is not None:
            step, state_sds, batch_sds, _, _, model = build_train_step(
                arch, None, B=tr["batch"], S=tr["seq"], rc=rc,
                trc=TrainRunConfig(opt=self.opt))
            params_sds = state_sds.params

            def bench_train_step(state, batch):
                return step(state, batch)
            aot("train", bench_train_step, state_sds, batch_sds, donate=(0,))
        if ev is not None:
            from repro.models import build
            from repro.runtime.specs import train_batch_specs
            model = build(arch, rc)
            params_sds = params_sds or model.init_eval_shape()

            def bench_eval_loss(params, batch):
                return model.loss(params, batch)
            aot("eval", bench_eval_loss, params_sds,
                train_batch_specs(arch, ev["batch"], ev["seq"]))
        if pf is not None:
            prefill, p_sds, pre_sds, _, _ = build_prefill_step(
                arch, None, B=pf["batch"], S=pf["seq"])
            params_sds = params_sds or p_sds

            def bench_prefill(params, batch):
                return prefill(params, batch)
            aot("prefill", bench_prefill, p_sds, pre_sds)
        if dc is not None:
            decode, d_sds, cache_sds, dec_sds, _, _ = build_decode_step(
                arch, ShapeConfig("bench_decode", "decode", dc["cache"],
                                  dc["batch"]), None)

            def bench_decode(params, cache, batch):
                return decode(params, cache, batch)
            aot("decode", bench_decode, d_sds, cache_sds, dec_sds,
                donate=(1,))
            self.cache_len = dc["cache"]
        self.params_sds = params_sds
        self._phase("compile_steps")

        # the weights: train state and deployed weights in one call
        nl, vs = m["num_hidden_layers"], m["vocab_size"]

        self.init_fn = jax.jit(lambda k: W.init_params(
            params_sds, k, n_layers=nl, vocab_size=vs))

        def bench_init(k):
            p = W.init_params(params_sds, k, n_layers=nl, vocab_size=vs)
            out = {"serve": p}
            if tr is not None:
                from repro.optim.adamw import init_state
                out["state"] = init_state(jax.tree.map(jnp.copy, p))
            return out
        with self.spans("compile"):
            made = jax.jit(bench_init)(self.key)
        self.serve_params = made["serve"]
        self.state = made.get("state")
        del made
        jax.block_until_ready((self.state, self.serve_params))
        self._phase("init_weights")

        self._helpers()
        with self.spans("warmup"):
            plane = self._plane(repeats=1)
            self.readings_due = tr is not None
            plane.run()
        jax.block_until_ready((self.state, self.serve_params))
        self.resident = sum(x.nbytes for x in jax.live_arrays())
        self._phase("warmup_workflow")
        self.plane = self._plane(self.traffic["repeats_cap"])
        self._phase("window_plane")

    def _helpers(self):
        import jax
        import jax.numpy as jnp
        m, b1 = self.m, self.opt.b1
        st = {s["kind"]: s for s in self.stages}
        vs = m["vocab_size"]

        def bench_data(key, w):
            dk = jax.random.fold_in(key, 1)
            out = {}
            if "train" in st:
                s = st["train"]
                toks = W.tokens(jax.random.fold_in(dk, 0), w,
                                (s["steps"], s["batch"], s["seq"] + 1), vs)
                out["train"] = [W.lm_batch(toks[i]) for i in range(s["steps"])]
            if "eval" in st:
                s = st["eval"]
                out["eval"] = W.lm_batch(W.tokens(
                    jax.random.fold_in(dk, 1), w, (s["batch"], s["seq"] + 1), vs))
            if "prefill" in st:
                s = st["prefill"]
                out["prompts"] = W.tokens(jax.random.fold_in(dk, 2), w,
                                          (s["batch"], s["seq"]), vs)
            return out
        data = jax.jit(bench_data)
        self.data_fn = lambda w: data(self.key, w)

        def bench_pad_cache(cache, logits):
            pad = self.cache_len - cache["k"].shape[2]
            out = dict(cache)
            for k in ("k", "v"):
                out[k] = jnp.pad(cache[k], ((0, 0), (0, 0), (0, pad),
                                            (0, 0), (0, 0)))
            return out, jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        self.pad_fn = jax.jit(bench_pad_cache)
        self.greedy_fn = jax.jit(
            lambda lg: jnp.argmax(lg[:, -1:], -1).astype(jnp.int32))
        self.concat_fn = jax.jit(lambda toks: jnp.concatenate(toks, 1))
        self.first_grad_fn = jax.jit(
            lambda mm: jax.tree.map(
                lambda x: jnp.sqrt(jnp.sum(x * x)) / (1 - b1), mm))
        self.change_fn = jax.jit(
            lambda p, p0: jax.tree.map(
                lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p, p0))

    # -- the stages ----------------------------------------------------------
    def _run_stage(self, kind, ns):
        import jax
        c = self.ctx[ns]
        spans = self.spans
        if kind == "data_prep":
            with spans("stage_glue"):
                c["w"] = self.n_workflows
                self.n_workflows += 1
                c.update(self.data_fn(c["w"]))
            with spans("payload_wait"):
                jax.block_until_ready(c.get("train") or c.get("prompts"))
            return {"workflow": c["w"]}
        if kind == "train":
            exe, losses = self.exe["train"], []
            with spans("stage_glue"):
                for i, batch in enumerate(c["train"]):
                    self.state, mets = exe(self.state, batch)
                    losses.append(mets["loss"])
                    if self.readings_due:
                        self._first_readings(i)
            with spans("payload_wait"):
                jax.block_until_ready((self.state, losses))
            if self.readings_due and len(losses) >= 3:
                self.first["losses"] = [float(x) for x in losses[:3]]
                self.readings_due = False
            return {"loss": losses[-1]}
        if kind == "eval":
            with spans("stage_glue"):
                loss = self.exe["eval"](self.state.params if self.state
                                        is not None else self.serve_params,
                                        c["eval"])
            with spans("payload_wait"):
                loss.block_until_ready()
            if self.recording:
                self.eval_losses.append(loss)
            return {"eval_loss": loss}
        if kind == "prefill":
            with spans("stage_glue"):
                logits, cache = self.exe["prefill"](
                    self.serve_params, {"tokens": c["prompts"]})
                c["cache"], tok = self.pad_fn(cache, logits)
                c["served"] = [tok]
            with spans("payload_wait"):
                jax.block_until_ready((c["cache"], tok))
            return {"first": tok}
        # decode
        exe, toks = self.exe["decode"], c["served"]
        with spans("stage_glue"):
            cache, tok = c.pop("cache"), toks[-1]
            for _ in range(self._stage("decode")["steps"]):
                logits, cache = exe(self.serve_params, cache,
                                    {"tokens": tok})
                tok = self.greedy_fn(logits)
                toks.append(tok)
            served = self.concat_fn(toks)
        with spans("payload_wait"):
            served.block_until_ready()
        if self.recording:
            self.served.append((c["w"], served))
        self.ctx.pop(ns, None)
        return {"served": served}

    def _first_readings(self, i):
        """The train state after steps 1 and 3 of the first workflow."""
        import jax
        if i == 0:
            self.first["grad"] = leaf_norms(jax.device_get(
                self.first_grad_fn(self.state.m)))
        if i == 2:
            self.first["change"] = leaf_norms(jax.device_get(
                self.change_fn(self.state.params, self.serve_params)))

    def _payload(self, kind):
        from repro.core.payloads import fn_payload

        def run(volume, task):
            w0 = time.perf_counter()
            if w0 >= self.deadline:
                raise WindowClosed
            ns = volume.name[:-len("-pvc")]
            out = fn_payload(lambda: self._run_stage(kind, ns))(volume, task)
            if self.recording:
                self.stamps.append((ns, kind, w0, time.perf_counter()))
            return out
        return run

    def _workflow(self):
        from repro.core.dag import Task, Workflow
        c = self.cfg["task"]
        ids = [s["id"] for s in self.stages]
        tasks = {}
        for i, s in enumerate(self.stages):
            tasks[s["id"]] = Task(
                id=s["id"], inputs=ids[i - 1:i], outputs=ids[i + 1:i + 2],
                cpu_m=c["cpu_m"], mem_mi=c["mem_mi"],
                payload=self._payload(s["kind"]))
        return Workflow(self.traffic["workflow"], tasks)

    def _plane(self, repeats: int):
        from repro.core.calibration import PaperCluster
        from repro.core.runner import ControlPlane
        cfg = self.cfg
        plane = ControlPlane(cfg["engine"],
                             cluster_cfg=PaperCluster(**cfg["cluster"]),
                             payload_mode="real", seed=self.seed,
                             scheduler=cfg["scheduler"],
                             admission_policy=cfg["admission_policy"])
        self.wf = self._workflow()
        tenants = list(self.traffic["tenants"])
        random.Random(self.seed).shuffle(tenants)
        for t in tenants:
            plane.add_stream(self.wf, repeats=repeats, tenant=t["name"],
                             arrival=self.traffic["arrival"])
        self.tenants = tenants
        return plane

    # -- the window ------------------------------------------------------------
    def window(self, seconds: float) -> dict:
        self.recording = True
        self.t0 = time.perf_counter()
        self.deadline = self.t0 + seconds
        run_until_closed(self.plane, self.spans)
        self.t_close = time.perf_counter()
        self.recording = False
        return self._summarise()

    def _summarise(self) -> dict:
        ends: Dict[str, float] = defaultdict(float)
        for ns, _k, _w0, w1 in self.stamps:
            ends[ns] = max(ends[ns], w1)
        self.done = finished_workflows(
            self.plane, [(t["name"], self.wf) for t in self.tenants])
        self.not_succeeded, self.order_bad, self.failed_ns = engine_faults(
            self.plane, self.done)
        finish = [ends[ns] for ns in self.done]
        by_kind: Dict[str, float] = defaultdict(float)
        n_kind: Dict[str, int] = defaultdict(int)
        for _ns, kind, w0, w1 in self.stamps:
            by_kind[kind] += w1 - w0
            n_kind[kind] += 1
        e2e = {}
        if finish:
            e2e["pipeline_wf_per_s"] = len(finish) / (max(finish) - self.t0)
        tr, dc = self._stage("train"), self._stage("decode")
        pf = self._stage("prefill")
        return {
            "end_to_end": e2e,
            "pods": len(self.stamps),
            "payload_s": sum(by_kind.values()),
            "loop_s": self.t_close - self.t0,
            "stage_s": dict(by_kind),
            "stage_runs": dict(n_kind),
            "model": self.m,
            "dtype": self.cfg["run"]["declared_dtype"],
            "train_shape": (tr["batch"], tr["seq"]) if tr else None,
            "decode_shape": ((dc["batch"], pf["seq"], dc["steps"])
                             if dc and pf else None),
            "train_steps": tr["steps"] if tr else 0,
        }

    def memory_peak_bytes(self) -> int:
        """Bytes resident before the window plus the largest step's own
        temporaries and outputs (``memory_analysis``), or the device's
        own peak counter where that reads higher."""
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        reckoned = self.resident + max(self.mem.values(), default=0)
        return int(max(reckoned, stats.get("peak_bytes_in_use", 0)))

    def release(self):
        """Free the program's device state before the reference runs."""
        self.state = None
        self.serve_params = None
        self.plane = None
        self.ctx.clear()
        self.exe.clear()

    def outcome(self):
        return len(self.done), len(self.failed_ns)

    # -- the comparison ------------------------------------------------------------
    def check(self) -> List[Check]:
        import jax
        lim = self.cfg["limits"]
        checks = [Check("tasks_not_succeeded", float(self.not_succeeded), 0.0),
                  Check("order_violations", float(self.order_bad), 0.0),
                  Check("completed_short",
                        float(self.traffic["min_completed"] - len(self.done)),
                        0.0)]
        if self.eval_losses:
            vals = [float(x) for x in jax.device_get(self.eval_losses)]
            bad = sum(1 for v in vals if not v == v or abs(v) == float("inf"))
            checks.append(Check("eval_loss_nonfinite", float(bad), 0.0))
        if self._stage("train") is not None:
            loss_gap, grad_gap, change_gap = self.train_numbers()
            checks += [Check("loss_gap", loss_gap, lim["loss_gap"]),
                       Check("change_gap", change_gap, lim["change_gap"])]
            # read and reported, not compared: no control or fault
            # separates it from sound runs (PERF.md)
            self.readings["grad_gap"] = grad_gap
        if self.served:
            checks.append(Check("serve_gap", self.serve_gap(), lim["serve_gap"]))
        return checks

    def _ref_params(self):
        """The weights rebuilt from the seed, as the reference sees them."""
        return self.init_fn(W.seed_key(self.seed))

    def ref_batches(self, workflow: int, n: int):
        return self.data_fn(workflow)["train"][:n]

    def train_numbers(self, dt=None, rows=None):
        """(loss gap, first-gradient gap, change gap) of the program's
        first three steps against the reference. With ``dt`` the
        reference computed at that operand precision stands in for the
        program; with ``rows`` it uses only the first rows of each batch."""
        import statistics
        o = dict(self.cfg["optimizer"], decay_min_ndim=2)
        batches = self.ref_batches(0, 3)
        if self._want is None:
            self._want = self._ref_train(batches, o, None, None)
        want_l, want_g, want_c = self._want
        if dt is None and rows is None:
            got_l, got_g, got_c = (self.first["losses"], self.first["grad"],
                                   self.first["change"])
        else:
            got_l, got_g, got_c = self._ref_train(batches, o, dt, rows)
        med = statistics.median(want_g.values())
        keep = [k for k, v in want_g.items() if v >= 1e-3 * med]
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(got_l, want_l))
        return (loss_gap, worst_norm_gap(got_g, want_g, keep),
                worst_norm_gap(got_c, want_c, keep))

    def _ref_train(self, batches, o, dt, rows):
        """The reference's losses, first-gradient and change leaf norms."""
        import jax
        losses, first, p3 = ref.train_readings(
            self._ref_params(), batches, ref.make_grad_fn(self.m, dt), o,
            rows=rows)
        change = leaf_norms(jax.device_get(
            self.change_fn(p3, self._ref_params())))
        return losses, leaf_norms(first), change

    def sample_requests(self):
        """(workflow, row, served tokens) of a sample drawn from the seed."""
        import numpy as np
        import jax
        rows = []
        for w, toks in self.served:
            toks = np.asarray(jax.device_get(toks))
            rows += [(w, r, toks[r]) for r in range(toks.shape[0])]
        rng = random.Random(self.seed)
        n = min(self.cfg["serve_check"]["requests"], len(rows))
        return rng.sample(rows, n)

    def serve_gap(self, dt=None, alter: int = 0) -> float:
        """Widest gap by which a served token's reference logit lies
        below the reference's best at that position. With ``dt`` the
        token is the one the reference at that operand precision puts
        first (the control); with ``alter`` each served id is shifted by
        that much (a fault)."""
        import numpy as np
        import jax
        import jax.numpy as jnp
        sample = self.sample_requests()
        params0 = self._ref_params()
        p_len = self._stage("prefill")["seq"]
        block = self.cfg["serve_check"]["block"]
        m = self.m
        worst = 0.0
        fn = jax.jit(lambda p, t: ref.serve_logits(
            p, t, jnp.arange(p_len - 1, t.shape[1]), m))
        low = None
        if dt is not None:
            low = jax.jit(lambda p, t: ref.serve_logits(
                p, t, jnp.arange(p_len - 1, t.shape[1]), m, dt))
        for i in range(0, len(sample), block):
            part = sample[i:i + block]
            ws = sorted({w for w, _r, _t in part})
            prompts = {w: np.asarray(jax.device_get(
                self.data_fn(w)["prompts"])) for w in ws}
            toks = np.stack([np.concatenate([prompts[w][r], t[:-1]])
                             for w, r, t in part])
            served = np.stack([t for _w, _r, t in part])
            lg = np.asarray(jax.device_get(fn(params0, jnp.asarray(toks))))
            if low is not None:
                served = np.asarray(jax.device_get(
                    low(params0, jnp.asarray(toks)))).argmax(-1)
            served = (served + alter) % lg.shape[-1]
            best = lg.max(-1)
            got = np.take_along_axis(lg, served[..., None], -1)[..., 0]
            worst = max(worst, float((best - got).max()))
        return worst
