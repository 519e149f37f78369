"""Batched serving example: prefill a batch of prompts, decode tokens
with the KV cache, report tokens/s — then run the same thing as a
KubeAdaptor serving workflow (prefill pod -> decode pods).

  PYTHONPATH=src python examples/serve_batch.py [--arch qwen2-0.5b]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.dag import Task, Workflow
from repro.core.payloads import fn_payload
from repro.core.runner import run_experiment
from repro.models import RunConfig, build
from repro.runtime.compile_cache import use_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch).reduced()
    model = build(cfg, RunConfig())
    params = model.init(jax.random.PRNGKey(0))
    B, P, G = args.batch, args.prompt_len, args.gen

    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(key, (B, P), 0, cfg.vocab_size)

    decode = jax.jit(model.decode)

    # ---- plain serving loop ------------------------------------------
    t0 = time.perf_counter()
    logits, cache = model.prefill(params, {"tokens": prompts})
    # grow cache to hold generated tokens
    if "k" in cache:
        pad = ((0, 0), (0, 0), (0, G), (0, 0), (0, 0))
        cache["k"] = jnp.pad(cache["k"], pad)
        cache["v"] = jnp.pad(cache["v"], pad)
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    for _ in range(G - 1):
        logits, cache = decode(params, cache, {"tokens": tok})
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
    gen = jnp.concatenate(out, axis=1)
    jax.block_until_ready(gen)
    dt = time.perf_counter() - t0
    print(f"generated {B}x{G} tokens in {dt:.2f}s "
          f"({B * G / dt:.1f} tok/s greedy, CPU)")
    assert gen.shape == (B, G)
    assert int(cache["pos"]) == P + G - 1

    # ---- same thing as a KubeAdaptor serving workflow ------------------
    results = {}

    def prefill_pod():
        lg, ch = model.prefill(params, {"tokens": prompts})
        results["cache"] = ch
        results["first"] = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
        return {"prefill_tokens": int(B * P)}

    def decode_pod():
        ch, tok = results["cache"], results["first"]
        if "k" in ch:
            pad = ((0, 0), (0, 0), (0, G), (0, 0), (0, 0))
            ch["k"], ch["v"] = jnp.pad(ch["k"], pad), jnp.pad(ch["v"], pad)
        toks = [tok]
        for _ in range(G - 1):
            lg, ch = decode(params, ch, {"tokens": toks[-1]})
            toks.append(jnp.argmax(lg, -1).astype(jnp.int32))
        results["gen"] = jnp.concatenate(toks, axis=1)
        return {"generated": int(B * G)}

    tasks = {
        "prefill": Task(id="prefill", outputs=["decode"],
                        payload=fn_payload(prefill_pod), duration_s=1.0),
        "decode": Task(id="decode", inputs=["prefill"],
                       payload=fn_payload(decode_pod), duration_s=2.0),
    }
    wf = Workflow("serve", tasks)
    res = run_experiment("kubeadaptor", wf, repeats=1, payload_mode="real")
    rec = res.metrics.wf_record(wf.with_instance(0))
    print(f"serving workflow lifecycle (virtual): {rec.lifecycle:.1f}s, "
          f"order_consistent={res.metrics.order_consistent(wf.with_instance(0))}")
    assert results["gen"].shape == (B, G)
    print("OK")


if __name__ == "__main__":
    main()
