"""Operation and byte counts against a hand count for qwen2-0.5b, and
the peak table."""
import json

import pytest

from bench.tests.cpu_cell import REPO
from bench import peaks, work

M = json.loads((REPO / "bench/configs/mlpipe-qwen2-0.5b.json").read_text())["model"]

# one layer: q and o 896x896, k and v 896x128, gate/up/down 896x4864
LAYER = 896 * 896 * 2 + 896 * 128 * 2 + 3 * 896 * 4864      # 14,909,440
HEAD = 151936 * 896                                          # tied embedding
BIAS_NORM = 896 + 128 * 2 + 2 * 896                          # per layer


def test_matmul_and_stored_params_by_hand():
    assert LAYER == 14_909_440
    assert work.matmul_params(M) == 24 * LAYER + HEAD == 493_961_216
    assert work.stored_params(M) == 24 * (LAYER + BIAS_NORM) + HEAD + 896


def test_train_flops_by_hand():
    causal_pairs = 1024 * 1025 / 2
    attn = 24 * 4 * 14 * 64 * causal_pairs          # QK^T and PV, 2 flops each
    fwd = 2 * 493_961_216 * 1024 + attn
    assert work.train_flops(M, 4, 1024) == pytest.approx(3 * 4 * fwd, rel=1e-12)
    # about 3.1 GFLOP a token, 12.7 TFLOP a B4 S1024 step
    assert work.train_flops(M, 4, 1024) / 4096 == pytest.approx(3.096e9, rel=1e-3)


def test_decode_bytes_and_flops_by_hand():
    pos = 519.5
    cache = 24 * 8 * (pos + 1) * 2 * 64 * 2 * 2       # k and v, bf16
    want = work.stored_params(M) * 2 + cache
    assert work.decode_bytes(M, 8, pos, "bfloat16") == pytest.approx(want)
    assert work.decode_bytes(M, 8, pos, "bfloat16") == pytest.approx(1.039e9, rel=1e-3)
    flops = 8 * (2 * 493_961_216 + 24 * 4 * 14 * 64 * (pos + 1))
    assert work.decode_flops(M, 8, pos) == pytest.approx(flops)
    p = peaks.lookup("TPU v5 lite")
    # a decode step is bound by bandwidth: about 1.27 ms at 819 GB/s
    assert work.roofline_s(flops, want, p) == pytest.approx(want / 819e9)


def test_peak_table_v5e_and_unknown_kind():
    p = peaks.lookup("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p["source"]
    with pytest.raises(SystemExit, match="no peak table entry"):
        peaks.lookup("TPU v9 imaginary")
