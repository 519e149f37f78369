"""CPU rehearsal of the latent-attention MoE pipeline cell
(``dsv2lite.train8k``, kind ``ml_pipeline_mla_moe``) at a tiny size: the
run is well formed and correct, each fault the cell can have makes it
not correct, the routing counters reach the program's tracing and its
metrics; and the serve-only traffic runs through the unchanged
``ml_pipeline`` driver."""
import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import cpu_cell

CELL = "dsv2lite.train8k"
# the published structure at a tiny width: a dense layer and two MoE
# layers, 4 of 16 experts held, top-6, 2 shared, MLA with YaRN
TINY = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
            num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, n_routed_experts=4, vocab_size=512)


def tiny_root(dst):
    root = cpu_cell.tiny_root(dst)
    p = root / "bench/configs/mlpipe-deepseek-v2-lite.json"
    cfg = cpu_cell._load(p)
    cfg.update(TINY)
    cfg["deployment"].update(n_routed_experts=16, expert_offset=4)
    cfg["run"].update(attn_dense_max=32, attn_chunk=16)    # blocked at S 64
    cfg["serve_check"] = {"requests": 8, "block": 4}
    cpu_cell._dump(p, cfg)
    for name, shapes in (("train8k", {"train": dict(steps=3, seq=64),
                                      "eval": dict(seq=64),
                                      "prefill": dict(seq=32),
                                      "decode": dict(steps=4, cache=48)}),
                         ("serve", {"prefill": dict(seq=32),
                                    "decode": dict(steps=8, cache=48)})):
        p = root / f"bench/traffic/{name}.json"
        tr = cpu_cell._load(p)
        for s in tr["stages"]:
            s.update(shapes.get(s["kind"], {}))
        cpu_cell._dump(p, tr)
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench_mla_moe"))


def test_cell_runs_through_the_control_plane(root):
    r = cpu_cell.run(root, CELL, seconds=3.0)
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "pipeline_wf_per_s"}
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    for name in ("loss_gap", "change_gap", "serve_gap", "moe_slots_dropped",
                 "eval_loss_nonfinite", "order_violations"):
        assert name in r["checks"]
    assert r["checks"]["moe_slots_dropped"]["value"] == 0.0


def _patch_train(monkeypatch, wrap):
    from repro.runtime import train
    real = train.build_train_step

    def build(*a, **kw):
        step, *rest = real(*a, **kw)
        return (wrap(step), *rest)
    monkeypatch.setattr(train, "build_train_step", build)


def _failed(r, name):
    return r["correct"] is False and \
        r["checks"][name]["value"] > r["checks"][name]["limit"]


def test_state_left_unchanged_is_not_correct(root, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            return state, step(state, batch)[1]
        return unchanged
    _patch_train(monkeypatch, wrap)
    assert _failed(cpu_cell.run(root, CELL, seconds=2.0), "change_gap")


def test_half_batch_is_not_correct(root, monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _patch_train(monkeypatch, wrap)
    assert _failed(cpu_cell.run(root, CELL, seconds=2.0), "loss_gap")


def test_dropped_slots_are_not_correct(root, monkeypatch):
    """A layer that drops routed slots reports them; one is enough."""
    def wrap(step):
        def dropping(state, batch):
            state, mets = step(state, batch)
            return state, dict(mets, **{"moe.slots_dropped":
                                        mets["moe.slots_dropped"] + 1})
        return dropping
    _patch_train(monkeypatch, wrap)
    assert _failed(cpu_cell.run(root, CELL, seconds=2.0), "moe_slots_dropped")


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.runtime import serve
    real = serve.build_decode_step

    def build(*a, **kw):
        decode, *rest = real(*a, **kw)

        def shifted(params, cache, batch):
            logits, cache, mets = decode(params, cache, batch)
            return jnp.roll(logits, 1, axis=-1), cache, mets
        return (shifted, *rest)
    monkeypatch.setattr(serve, "build_decode_step", build)
    assert _failed(cpu_cell.run(root, CELL, seconds=2.0), "serve_gap")


def test_traced_window_counts_routing_and_the_counter_metric_reads_it(
        root, tmp_path):
    """A window under a profiler session counts every step's routing
    totals in the program's tracing, and the counter metric reads them
    (the device shares need a device trace, which a CPU run has not)."""
    from repro.core import tracing
    cell = harness.load_cell(root, CELL)
    harness.use_compile_cache(root)
    d = harness.load_driver(cell)(cell, 2_200_000_031, harness.Spans(False))
    d.setup()
    with jax.profiler.trace(str(tmp_path)):
        win = d.window(2.0)
    snap = tracing.snapshot()
    steps = snap["moe.slots_held"]["count"]
    assert steps >= 3 and steps % 3 == 0                    # 3 a workflow
    assert snap["moe.load_max"]["count"] == steps
    assert snap["moe.experts_touched"]["count"] % 4 == 0    # 4 a workflow
    assert snap["moe.slots_dropped"]["total"] == 0
    want = snap["moe.load_max"]["total"] / (
        snap["moe.slots_held"]["total"] / cell.config["n_routed_experts"])
    rec = harness.Record(cell, cpu_cell.cpu_gate(1), win, {"modules": {}})
    got = harness.load_reader(cell, "moe_load_max_over_mean")(rec)
    assert got == pytest.approx(want) and got >= 1.0
    assert harness.load_reader(cell, "mfu.mla_moe_train")(rec) is None


def test_device_shares_read_the_trace_and_counters(root, monkeypatch):
    from bench import program_spans, work, work_mla_moe
    cell = harness.load_cell(root, CELL)
    m = cell.config
    window = {"model": m, "dtype": "bfloat16", "train_shape": (2, 64),
              "decode_shape": (4, 32, 4)}
    trace = {"modules": {"jit_bench_train_step": {"seconds": 0.3, "runs": 3},
                         "jit_bench_decode": {"seconds": 0.004, "runs": 4}}}
    rec = harness.Record(cell, cpu_cell.cpu_gate(1), window, trace)
    counters = {"moe.slots_held": {"count": 3, "total": 3000, "max": 1000},
                "moe.experts_touched": {"count": 4, "total": 24, "max": 6}}
    monkeypatch.setattr(program_spans, "totals", lambda: counters)
    peak = rec.device.peaks["bf16_flops"]
    train = harness.load_reader(cell, "mfu.mla_moe_train")(rec)
    assert train == pytest.approx(
        100 * work_mla_moe.train_flops(m, 2, 64, 1000) / (0.1 * peak))
    decode = harness.load_reader(cell, "mfu.mla_moe_decode")(rec)
    pos = 32 + 1.5
    least = work.roofline_s(work_mla_moe.decode_flops(m, 4, pos, 6),
                            work_mla_moe.decode_bytes(m, 4, pos, 6, "bfloat16"),
                            rec.device.peaks)
    assert decode == pytest.approx(100 * least / 0.001)
    monkeypatch.setattr(program_spans, "totals", lambda: {})
    for name in ("mfu.mla_moe_train", "mfu.mla_moe_decode",
                 "moe_load_max_over_mean"):
        assert harness.load_reader(cell, name)(rec) is None


def test_work_counts_the_published_sizes():
    """The reckoning of the cut (535 M parameters held, 81.0 M in the
    dense layer, 13.8 M of attention a layer) from the work functions."""
    from bench import work_mla_moe
    m = harness.load_cell(cpu_cell.REPO, CELL).config
    attn = work_mla_moe.mla_params(m)
    assert attn == pytest.approx(13.76e6, rel=1e-3)
    assert attn + 3 * 2048 * 10944 == pytest.approx(81.0e6, rel=2e-3)
    held = (work_mla_moe.dense_params(m) + 4 * 8 * work_mla_moe.expert_params(m)
            + 12800 * 2048)                         # + the embedding
    assert held == pytest.approx(535e6, rel=5e-3)


def test_serve_traffic_runs_through_the_unchanged_pipeline_driver(root):
    r = cpu_cell.run(root, "mlpipe.serve", seconds=2.0)
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"setup_s", "pipeline_wf_per_s"}
    assert "serve_gap" in r["checks"] and "loss_gap" not in r["checks"]
    assert harness.load_cell(root, "mlpipe.serve").config["kind"] == "ml_pipeline"
