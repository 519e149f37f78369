"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a short trace recorded on a TPU v5e
(``bench/record_trace.py``: paper4.flood, a 0.3 s window)."""
import pytest

from bench import trace
from bench.tests.cpu_cell import REPO

RECORDED = REPO / "bench/tests/data/paper4_flood.xplane.pb"


def test_union_clip_gaps():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert trace.total(u) == 7
    assert trace.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace.gaps(u, -1, 12) == [(-1, 0), (3, 5), (9, 12)]


def test_idle_time_goes_to_the_most_specific_span():
    gap_list = [(0, 10), (20, 30)]
    spans = {"engine_loop": [(0, 30)], "payload": [(4, 6), (25, 40)],
             "compile": [(5, 8)]}
    got = trace.attribute(gap_list, spans)
    # compile 5..8, payload 4..5, engine loop 0..4 and 8..10
    assert got[0] == pytest.approx({"compile": 3, "payload": 1,
                                    "engine_loop": 6})
    assert got[1] == pytest.approx({"engine_loop": 5, "payload": 5})
    assert trace.attribute([(0, 4)], {"window": [(1, 2)]}) == [
        pytest.approx({"other": 3, "window": 1})]


def test_module_and_op_names_are_stable():
    assert trace.module_name("jit_bench_train_step(17)") == "jit_bench_train_step"
    assert trace.module_name("jit_body") == "jit_body"
    assert trace.op_name("%fusion.9 = f32[512,512]{1,0} fusion(%copy.11)") == "%fusion.9"


def test_recorded_v5e_trace():
    r = trace.reduce_file(str(RECORDED))
    assert r["n_devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"] < 1.0
    assert r["device_ops"] and all(s > 0 for _n, s in r["device_ops"])
    assert len(r["idle_gaps"]) <= trace.TOP
    names = set(trace.SPAN_PRIORITY) | {"other"}
    assert {who for who, _s in r["idle_gaps"]} <= names
    idle = sum(r["idle_by_span"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-6)
    assert "jit_body" in r["modules"]
    assert r["modules"]["jit_body"]["runs"] >= 1
