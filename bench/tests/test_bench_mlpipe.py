"""CPU rehearsal of the ML pipeline cell at a tiny size: the run is
well formed, each fault the cell can have makes it not correct, and the
lower-precision control reads far above the program."""
import jax.numpy as jnp
import pytest

from bench import harness, oracle
from bench.tests import cpu_cell


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cpu_cell.tiny_root(tmp_path_factory.mktemp("bench_mlpipe"))


def test_mlpipe_runs_through_the_control_plane(root):
    r = cpu_cell.run(root, "mlpipe.train", seconds=2.0)
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "pipeline_wf_per_s"}
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["compiles_in_window"] == 0
    for name in ("loss_gap", "change_gap", "serve_gap",
                 "eval_loss_nonfinite", "order_violations"):
        assert name in r["checks"]
    assert "grad_gap" in r["readings"]


def _patch_train(monkeypatch, wrap):
    from repro.runtime import train
    real = train.build_train_step

    def build(*a, **kw):
        step, *rest = real(*a, **kw)
        return (wrap(step), *rest)
    monkeypatch.setattr(train, "build_train_step", build)


def test_state_left_unchanged_is_not_correct(root, monkeypatch):
    def wrap(step):
        def unchanged(state, batch):
            _, mets = step(state, batch)
            return state, mets
        return unchanged
    _patch_train(monkeypatch, wrap)
    r = cpu_cell.run(root, "mlpipe.train", seconds=2.0)
    assert r["correct"] is False
    assert r["checks"]["change_gap"]["value"] > r["checks"]["change_gap"]["limit"]


def test_half_batch_is_not_correct(root, monkeypatch):
    def wrap(step):
        def half(state, batch):
            n = batch["tokens"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half
    _patch_train(monkeypatch, wrap)
    r = cpu_cell.run(root, "mlpipe.train", seconds=2.0)
    assert r["correct"] is False
    assert r["checks"]["loss_gap"]["value"] > r["checks"]["loss_gap"]["limit"]


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.runtime import serve
    real = serve.build_decode_step

    def build(*a, **kw):
        decode, *rest = real(*a, **kw)

        def shifted(params, cache, batch):
            logits, cache = decode(params, cache, batch)
            return jnp.roll(logits, 1, axis=-1), cache
        return (shifted, *rest)
    monkeypatch.setattr(serve, "build_decode_step", build)
    r = cpu_cell.run(root, "mlpipe.train", seconds=2.0)
    assert r["correct"] is False
    assert r["checks"]["serve_gap"]["value"] > r["checks"]["serve_gap"]["limit"]


def test_lower_precision_control_reads_far_above_the_program(root):
    """The control (the reference with float8 operands) against the
    program on the same seeds. At this size the readings are smaller
    than at the cell's own, where the chip runs set the limits (PERF.md)
    and the control fails them; here the program passes the limits and
    the control reads at least 3x the program on a compared number."""
    cell = harness.load_cell(root, "mlpipe.train")
    harness.use_compile_cache(root)
    lim = cell.config["limits"]
    for seed in (11, 2_147_483_659, 5):
        d = harness.load_driver(cell)(cell, seed, harness.Spans(False))
        d.setup()
        d.window(2.0)
        d.release()
        assert d.served, "no request was served in the window"
        got = oracle.readings(d, cell, control=True)
        prog, ctrl = got["program"], got["control"]
        assert all(prog[k] <= lim[k] for k in lim), prog
        assert any(ctrl[k] >= 3 * max(prog[k], 1e-6) for k in
                   ("loss_gap", "serve_gap")), (prog, ctrl)
        assert got["fault_half_batch"]["loss_gap"] > lim["loss_gap"]
        assert got["fault_altered"]["serve_gap"] > lim["serve_gap"]
