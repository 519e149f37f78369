"""Host spans and counters of the program, recorded only while a JAX
profiler session is on.

A span site reads ``with tracing.span("pod.payload", task=...):``. With
the profiler off it costs one check (``TraceAnnotation.is_enabled()``,
none at all before jax is imported) and records nothing, so untraced
runs pay nothing more. While a session is on, each span is a
``jax.profiler.TraceAnnotation``, with its keyword arguments attached:
it lands in the trace's host plane beside the caller's own spans, on
the device trace's clock. Each span also adds to totals kept in memory
per name: count, total seconds, self seconds (total minus the time its
child spans cover) and the longest single span. ``snapshot()`` returns
the totals of the latest session; the first span of a new session
starts fresh ones.

The spans, from the outside in:

    sim.run          the control plane's event loop (``Sim.run``)
    pod.payload      one pod's payload, as the paper times it
                     (``Cluster._start_one``), args namespace and task
    payload.input    resident operand, built on the first call (matmul_payload)
    payload.build    that build, inside payload.input: numpy draw and
                     upload, once per closure and process (its count is
                     how often the resident operand misses)
    payload.compute  dispatch and device wait           (matmul_payload)
    payload.output   read-back and the shared-volume hand-off
    gc               one garbage collection, arg generation

Counters ride on the same sessions: ``tracing.count("moe.slots_held",
n)`` adds ``n`` (an int, or a device scalar, read only while a session
is on) to that name's total, and ``snapshot()`` gives a counter's
count of calls, ``total`` and ``max``. With the profiler off a call
costs one check and reads nothing. The benchmark's MoE pipeline counts,
after each stage's wait:

    moe.slots_held       slots routed to the experts held here, per
                         train step, summed over MoE layers
    moe.slots_dropped    of those, the slots not computed (train and
                         decode steps); 0 on the dropless path
    moe.load_max         each MoE layer's largest held-expert load, per
                         train step, summed over layers
    moe.experts_touched  held experts given any slot, per decode step,
                         summed over layers

``gc`` comes from a ``gc.callbacks`` hook that the first span of a
session installs and that goes once the profiler is found off, by the
hook itself or by the next span site: untraced runs run no Python
callback on each collection. A ``gc`` span interrupts whatever span is
open; its time stays in that span's self time.

The profiler session is one per process, so its totals are too. This
module never imports jax: a control plane with virtual payloads stays
jax-free.
"""
from __future__ import annotations

import gc
import importlib
import sys
import threading
from contextlib import nullcontext
from time import perf_counter
from typing import Dict, List

_OFF = nullcontext()

_is_enabled = None       # jax's TraceAnnotation.is_enabled, once bound
_Annotation = None       # jax.profiler.TraceAnnotation
_state = None            # jax's profiler state: its session object

_lock = threading.RLock()      # a gc span may close inside _add
_totals: Dict[str, List[float]] = {}   # name -> [count, total, self, max]
_counts: Dict[str, List[int]] = {}     # counter name -> [calls, total, max]
_session = None          # the profiler session the totals cover
_live = False            # a session is being recorded
_hooked = False          # _gc_hook is in gc.callbacks
_gc_open = None          # (annotation, start) of the collection running


class _Open(threading.local):
    """The open spans of one thread, innermost last."""

    def __init__(self):
        self.stack: List[_Span] = []


_tls = _Open()


def _bind():
    """Bind jax's profiler once jax has been imported by someone else."""
    global _is_enabled, _Annotation, _state
    if "jax" not in sys.modules:
        return None
    try:
        profiler = importlib.import_module("jax.profiler")
    except ImportError:               # jax is still being imported
        return None
    _Annotation = profiler.TraceAnnotation
    _state = getattr(sys.modules.get("jax._src.profiler"),
                     "_profile_state", None)
    _is_enabled = _Annotation.is_enabled
    return _is_enabled


def _on() -> bool:
    f = _is_enabled
    if f is None:
        f = _bind()
        if f is None:
            return False
    return f()


def span(name: str, **args):
    """A context manager timing ``name``; a no-op unless profiling."""
    if not _on():
        if _hooked:
            _end()
        return _OFF
    return _Span(name, args)


def count(name: str, n) -> None:
    """Add ``n`` to the counter ``name``; a no-op unless profiling, and
    ``n`` (a device scalar, say) is read only then."""
    if not _on():
        if _hooked:
            _end()
        return
    n = int(n)
    with _lock:
        if not _live or _session_key() is not _session:
            _begin()
        rec = _counts.get(name)
        if rec is None:
            rec = _counts[name] = [0, 0, n]
        rec[0] += 1
        rec[1] += n
        rec[2] = max(rec[2], n)


def snapshot() -> Dict[str, Dict[str, float]]:
    """The latest profiler session's totals: a span's name -> count,
    total_s, self_s and max_s; a counter's -> count (calls), total and
    max. Empty before any span or count was recorded."""
    with _lock:
        out = {name: {"count": int(c), "total_s": t, "self_s": s,
                      "max_s": m}
               for name, (c, t, s, m) in _totals.items()}
        out.update({name: {"count": c, "total": t, "max": m}
                    for name, (c, t, m) in _counts.items()})
        return out


class _Span:
    __slots__ = ("name", "args", "ann", "t0", "child")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.args = args

    def __enter__(self):
        if not _live or _session_key() is not _session:
            _begin()
        self.ann = _Annotation(self.name, **self.args)
        self.ann.__enter__()
        self.child = 0.0
        _tls.stack.append(self)
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        dt = perf_counter() - self.t0
        self.ann.__exit__(*exc)
        stack = _tls.stack
        stack.pop()
        if stack:
            stack[-1].child += dt
        _add(self.name, dt, dt - self.child)
        return False


def _session_key():
    return None if _state is None else _state.profile_session


def _begin() -> None:
    """At the first span or count of a profiler session: fresh totals
    and the gc hook."""
    global _totals, _counts, _session, _live, _hooked
    with _lock:
        key = _session_key()
        if _live and key is _session:        # another thread began it
            return
        _totals = {}
        _counts = {}
        _session = key
        _live = True
        if not _hooked:
            gc.callbacks.append(_gc_hook)
            _hooked = True


def _end() -> None:
    """The profiler was found off by a span site: the session is over."""
    global _live, _hooked
    _live = False
    gc.callbacks.remove(_gc_hook)
    _hooked = False


def _add(name: str, dt: float, self_dt: float) -> None:
    with _lock:
        rec = _totals.get(name)
        if rec is None:
            rec = _totals[name] = [0, 0.0, 0.0, 0.0]
        rec[0] += 1
        rec[1] += dt
        rec[2] += self_dt
        if dt > rec[3]:
            rec[3] = dt


def _gc_hook(phase: str, info: dict) -> None:
    global _gc_open, _live, _hooked
    if phase == "start":
        if not _on():
            _live = False
            callbacks = gc.callbacks
            # the collector walks its list by index: removing this hook
            # while a later one is still due would skip that one
            if callbacks and callbacks[-1] is _gc_hook:
                callbacks.pop()
                _hooked = False
            return
        if not _live or _session_key() is not _session:
            _begin()
        ann = _Annotation("gc", generation=info["generation"])
        ann.__enter__()
        _gc_open = (ann, perf_counter())
    elif _gc_open is not None:
        ann, t0 = _gc_open
        _gc_open = None
        dt = perf_counter() - t0
        ann.__exit__(None, None, None)
        _add("gc", dt, dt)

