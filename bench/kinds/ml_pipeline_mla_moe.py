"""Driver for ML pipeline workflows of a latent-attention MoE model
(DeepSeek-V2, ``deepseek_v2`` configs): the ``ml_pipeline`` driver with
the model's own program configuration, latent cache, reference and
routing counters.

The configuration file holds the published ``config.json`` keys at its
top level, with the chip's share of the deployment in ``deployment``:
``n_routed_experts`` there is the published count the router spans, the
top-level one the experts held here (``expert_offset`` the first);
``vocab_size`` is the slice of the vocabulary held here, ids 0 .. V - 1,
from which the traffic draws. Stages, shapes and the window are as in
``ml_pipeline``; the train and decode steps also return the MoE layer's
routing totals. After each stage's wait they go to the program's
counters (``tracing.count``), which read them only in a traced run, and
the window keeps the dropped-slot totals for the check.

Correct means what it means for ``ml_pipeline``, against
``bench/ref/deepseek_v2.py``, and no routed slot dropped in the window
(``moe_slots_dropped``, limit 0).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from bench.harness import Check
from bench.kinds import ml_pipeline
from bench.kinds.ml_pipeline import leaf_norms
from bench.ref import deepseek_v2 as ref
from bench.ref import weights as W
from repro.configs.base import ArchConfig, YarnScaling

# what the program implements of the published config; anything else is
# refused rather than run as something it is not
SUPPORTED = {"model_type": "deepseek_v2", "q_lora_rank": None,
             "hidden_act": "silu", "attention_bias": False,
             "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
             "topk_method": "greedy", "scoring_func": "softmax"}


def arch_config(m: dict, name: str) -> ArchConfig:
    """The program's ArchConfig for the configuration's published keys
    and the chip's share of the deployment."""
    for k, v in SUPPORTED.items():
        if m.get(k) != v:
            raise ValueError(f"{name}: {k}={m.get(k)!r}, the program runs {v!r}")
    rs, dep = m["rope_scaling"], m["deployment"]
    if rs["type"] != "yarn":
        raise ValueError(f"{name}: rope_scaling type {rs['type']!r}")
    return ArchConfig(
        name=name, family="moe", n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"],
        head_dim=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        tie_embeddings=m["tie_word_embeddings"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"],
        n_experts=dep["n_routed_experts"], top_k=m["num_experts_per_tok"],
        expert_d_ff=m["moe_intermediate_size"],
        n_shared_experts=m["n_shared_experts"],
        shared_expert_d_ff=m["n_shared_experts"] * m["moe_intermediate_size"],
        moe_dropless=True, norm_topk_prob=m["norm_topk_prob"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        moe_aux="seq" if m["seq_aux"] else "switch",
        aux_loss_alpha=m["aux_loss_alpha"],
        n_experts_held=m["n_routed_experts"],
        expert_offset=dep["expert_offset"],
        first_k_dense=m["first_k_dense_replace"],
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        rope_scaling=YarnScaling(
            factor=float(rs["factor"]),
            original_max_position=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]),
            mscale_all_dim=float(rs["mscale_all_dim"])))


TRAIN_COUNTERS = ("slots_held", "slots_dropped", "load_max")
DECODE_COUNTERS = ("slots_dropped", "experts_touched")


class Driver(ml_pipeline.Driver):
    def __init__(self, cell, seed: int, spans):
        # the published keys sit at the file's top level: they are the
        # model block
        cell = dataclasses.replace(cell, config=dict(cell.config,
                                                     model=cell.config))
        super().__init__(cell, seed, spans)
        self.dropped: List = []          # the window's per-step device totals

    # -- set-up ----------------------------------------------------------------
    def setup(self):
        """As ``ml_pipeline.Driver.setup``, with this model's program
        configuration, attention blocking and a decode step that also
        returns its routing totals."""
        import jax
        import jax.numpy as jnp
        from repro.configs.base import ShapeConfig
        from repro.models import RunConfig, build
        from repro.optim.adamw import OptConfig, init_state
        from repro.runtime.serve import build_decode_step, build_prefill_step
        from repro.runtime.specs import train_batch_specs
        from repro.runtime.train import TrainRunConfig, build_train_step

        self._phase("imports")
        cfg, m = self.cfg, self.m
        arch = arch_config(m, cfg["name"])
        run = cfg["run"]
        rc = RunConfig(param_dtype=run["param_dtype"],
                       compute_dtype=run["compute_dtype"],
                       remat=run["remat"] != "none", remat_policy=run["remat"],
                       attn_chunk=run["attn_chunk"],
                       attn_dense_max=run["attn_dense_max"],
                       moe_group=run["moe_group"])
        self.opt = OptConfig(**{k: cfg["optimizer"][k] for k in (
            "lr", "warmup_steps", "total_steps", "min_lr_ratio", "b1", "b2",
            "eps", "weight_decay", "clip_norm")})
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

        tr, ev = self._stage("train"), self._stage("eval")
        pf, dc = self._stage("prefill"), self._stage("decode")
        params_sds = build(arch, rc).init_eval_shape()
        if tr is not None:
            step, state_sds, batch_sds, *_ = build_train_step(
                arch, None, B=tr["batch"], S=tr["seq"], rc=rc,
                trc=TrainRunConfig(opt=self.opt))

            def bench_train_step(state, batch):
                return step(state, batch)
            aot("train", bench_train_step, state_sds, batch_sds, donate=(0,))
        if ev is not None:
            model = build(arch, rc)

            def bench_eval_loss(params, batch):
                return model.loss(params, batch)
            aot("eval", bench_eval_loss, params_sds,
                train_batch_specs(arch, ev["batch"], ev["seq"]))
        if pf is not None:
            prefill, _, pre_sds, _, _ = build_prefill_step(
                arch, None, B=pf["batch"], S=pf["seq"], rc=rc)

            def bench_prefill(params, batch):
                return prefill(params, batch)
            aot("prefill", bench_prefill, params_sds, pre_sds)
        if dc is not None:
            decode, _, cache_sds, dec_sds, _, _ = build_decode_step(
                arch, ShapeConfig("bench_decode", "decode", dc["cache"],
                                  dc["batch"]), None, rc=rc, with_stats=True)

            def bench_decode(params, cache, batch):
                return decode(params, cache, batch)
            aot("decode", bench_decode, params_sds, cache_sds, dec_sds,
                donate=(1,))
            self.cache_len = dc["cache"]
        self.params_sds = params_sds
        self._phase("compile_steps")

        nl, vs = m["num_hidden_layers"], m["vocab_size"]
        self.init_fn = jax.jit(lambda k: W.init_params(
            params_sds, k, n_layers=nl, vocab_size=vs))

        def bench_init(k):
            p = W.init_params(params_sds, k, n_layers=nl, vocab_size=vs)
            out = {"serve": p}
            if tr is not None:
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
        super()._helpers()

        def bench_pad_cache(cache, logits):
            # every latent entry (L, B, T, .) of every stack, padded on T
            def pad(x):
                return jnp.pad(x, ((0, 0), (0, 0),
                                   (0, self.cache_len - x.shape[2]), (0, 0)))
            out = {k: v if k == "pos" else jax.tree.map(pad, v)
                   for k, v in cache.items()}
            return out, jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        self.pad_fn = jax.jit(bench_pad_cache)

    # -- the stages ----------------------------------------------------------
    def _run_stage(self, kind, ns):
        """Train and decode as in ``ml_pipeline``, keeping each step's
        routing totals; the other stages are ``ml_pipeline``'s."""
        if kind not in ("train", "decode"):
            return super()._run_stage(kind, ns)
        import jax
        c, spans = self.ctx[ns], self.spans
        if kind == "train":
            exe, losses, mets = self.exe["train"], [], []
            with spans("stage_glue"):
                for i, batch in enumerate(c["train"]):
                    self.state, met = exe(self.state, batch)
                    losses.append(met["loss"])
                    mets.append(met)
                    if self.readings_due:
                        self._first_readings(i)
            with spans("payload_wait"):
                jax.block_until_ready((self.state, losses))
            self._count(mets, TRAIN_COUNTERS)
            if self.readings_due and len(losses) >= 3:
                self.first["losses"] = [float(x) for x in losses[:3]]
                self.readings_due = False
            return {"loss": losses[-1]}
        exe, toks, mets = self.exe["decode"], c["served"], []
        with spans("stage_glue"):
            cache, tok = c.pop("cache"), toks[-1]
            for _ in range(self._stage("decode")["steps"]):
                logits, cache, met = exe(self.serve_params, cache,
                                         {"tokens": tok})
                tok = self.greedy_fn(logits)
                toks.append(tok)
                mets.append(met)
            served = self.concat_fn(toks)
        with spans("payload_wait"):
            served.block_until_ready()
        self._count(mets, DECODE_COUNTERS)
        if self.recording:
            self.served.append((c["w"], served))
        self.ctx.pop(ns, None)
        return {"served": served}

    def _count(self, mets, names):
        """Each step's totals to the program's counters (read only in a
        traced run); the window's dropped slots kept for the check."""
        from repro.core import tracing
        for met in mets:
            for name in names:
                tracing.count("moe." + name, met["moe." + name])
            if self.recording:
                self.dropped.append(met["moe.slots_dropped"])

    # -- the comparison ------------------------------------------------------------
    def check(self) -> List[Check]:
        import jax
        checks = super().check()
        dropped = sum(int(x) for x in jax.device_get(self.dropped))
        checks.append(Check("moe_slots_dropped", float(dropped),
                            self.cfg["limits"]["moe_slots_dropped"]))
        return checks

    def _ref_train(self, batches, o, dt, rows):
        """The reference's losses, first-gradient and change leaf norms."""
        import jax
        losses, first, p3 = ref.train_readings(
            self._ref_params(), batches, ref.make_grad_fn(self.m, dt), o,
            rows=rows)
        change = leaf_norms(jax.device_get(
            self.change_fn(p3, self._ref_params())))
        return losses, leaf_norms(first), change

    def serve_gap(self, dt=None, alter: int = 0) -> float:
        """As ``ml_pipeline.Driver.serve_gap``, against this reference."""
        import numpy as np
        import jax
        import jax.numpy as jnp
        sample = self.sample_requests()
        params0 = self._ref_params()
        p_len = self._stage("prefill")["seq"]
        block = self.cfg["serve_check"]["block"]
        m = self.m

        def logits_fn(low):
            return jax.jit(lambda p, t: ref.serve_logits(
                p, t, jnp.arange(p_len - 1, t.shape[1]), m, low))
        fn = logits_fn(None)
        low = None if dt is None else logits_fn(dt)
        worst = 0.0
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
