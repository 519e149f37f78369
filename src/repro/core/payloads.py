"""Task payloads: the paper's stress emulator + real ML payloads.

A payload is ``fn(volume, task) -> None``; it reads upstream outputs
from the namespace SharedVolume and writes its own (the PV-mediated
data dependency of §3.2). Virtual-clock benchmarks use stress_payload
(markers only); the ML workflow examples run real jitted JAX steps.
"""
from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from repro.core import tracing


def stress_payload(volume, task):
    """task-emulator analogue: consume inputs, emit a completion marker."""
    if volume is None:
        return
    for dep in task.inputs:
        _ = volume.get(f"{dep}/out")        # data dependency read
    volume.put(f"{task.id}/out", {"task": task.id, "ok": True})


def matmul_payload(n: int = 256, iters: int = 4) -> Callable:
    """A real device payload (used in payload_mode='real'): ``iters``
    jitted (n, n) f32 matmul+tanh steps on the default JAX device.

    The input depends on ``n`` alone, so the closure builds it once, on
    its first call, and keeps it resident on the device for every pod
    after that."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def body(x):
        def step(h, _):
            return jnp.tanh(h @ h) * 0.5 + h * 0.5, None
        out, _ = jax.lax.scan(step, x, None, length=iters)
        return out

    operand = None

    def run(volume, task):
        nonlocal operand
        with tracing.span("payload.input"):
            if operand is None:
                with tracing.span("payload.build"):
                    operand = jnp.asarray(
                        np.random.default_rng(0).standard_normal((n, n)),
                        jnp.float32)
            # every pod passes the same array: body donates no argument
            x = operand
        with tracing.span("payload.compute"):
            y = body(x)
            y.block_until_ready()
        with tracing.span("payload.output"):
            if volume is not None:
                for dep in task.inputs:
                    _ = volume.get(f"{dep}/out")
                volume.put(f"{task.id}/out", np.asarray(y[0, :4]))

    return run


def fn_payload(fn: Callable[[], Optional[dict]]) -> Callable:
    """Wrap an arbitrary thunk (e.g. a jitted train step) as a payload.

    The thunk's result is returned, so that ``sim.measure_wall`` can wait
    for any device arrays in it before it stops the pod's clock."""

    def run(volume, task):
        result = fn()
        if volume is not None:
            for dep in task.inputs:
                _ = volume.get(f"{dep}/out")
            volume.put(f"{task.id}/out", result if result is not None else True)
        return result

    return run
