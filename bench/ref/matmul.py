"""Plain reference for the matmul-recurrence task payload.

The payload draws its (n, n) input from ``numpy.random.default_rng(0)``,
stores it as float32 and runs ``iters`` steps of
``h <- tanh(h @ h) / 2 + h / 2``; each pod writes ``h[0, :4]`` to its
volume. The reference runs the same recurrence in float64 on the host.
"""
from __future__ import annotations

import numpy as np


def payload_input(n: int) -> np.ndarray:
    return np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)


def quantize(x: np.ndarray, dtype) -> np.ndarray:
    """Round ``x`` to ``dtype`` with one per-tensor scale (amax to the
    format's largest finite value), back in float64."""
    import ml_dtypes
    top = float(ml_dtypes.finfo(dtype).max)
    scale = max(float(np.abs(x).max()), 1e-30) / top
    return (x / scale).astype(dtype).astype(np.float64) * scale


def recurrence(n: int, iters: int, operand_dtype=None) -> np.ndarray:
    """``h[0, :4]`` after ``iters`` steps, in float64; with
    ``operand_dtype`` each matmul's operands are rounded to it first
    (the lower-precision control)."""
    h = payload_input(n).astype(np.float64)
    for _ in range(iters):
        a = h if operand_dtype is None else quantize(h, operand_dtype)
        h = np.tanh(a @ a) * 0.5 + h * 0.5
    return h[0, :4]


def gap(got, ref) -> float:
    return float(np.abs(np.asarray(got, np.float64) - ref).max())
