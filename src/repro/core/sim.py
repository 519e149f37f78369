"""Discrete-event simulation core.

One single-threaded event queue drives the whole control plane
(cluster, informers, engines, pollers). Payloads can be:

  * virtual  — a declared duration advances the clock (paper-scale
               numbers reproduce instantly; used by benchmarks),
  * real     — the callable executes NOW (e.g. a jitted JAX step) and
               its measured wall-time becomes the virtual duration
               (used by the ML workflow examples and tests).

This "virtual time, real work" design is what lets a 1-core container
model a 6-node cluster faithfully: concurrency exists in virtual time,
while real payloads still run and produce real arrays.

Scale notes: each scheduled event is a ``__slots__`` record, not a
closure-capturing tuple; hot callers pass ``args=`` instead of
allocating a lambda per event. ``events_processed`` counts executed
events so benchmarks can report events/sec, and the ``note`` string is
kept on the record — a ``max_events`` overflow names the next pending
notes so runaway polling loops identify their culprit.

The queue is one binary heap of ``(t, seq, Event)``: pop order is
exactly ``(t, seq)``, so same-instant events run in the order they were
scheduled (FIFO tie-break).  A run that declines the head at its
horizon leaves the queue untouched, so a later ``run`` resumes it.

``run(until=...)`` leaves the clock at ``until`` even when the queue
drains early, so a horizon is a horizon regardless of load; the time
of the last *processed* event stays available as ``last_event_t``
(benchmarks report it as the makespan).
"""
from __future__ import annotations

import heapq
import itertools
import time
from typing import Any, Callable, List, Optional, Tuple

from repro.core import tracing


class Event:
    """One scheduled callback: ``fn(*args)`` at a point in virtual time."""

    __slots__ = ("fn", "args", "note", "daemon")

    def __init__(self, fn: Callable, args: Tuple, note: str, daemon: bool):
        self.fn = fn
        self.args = args
        self.note = note
        self.daemon = daemon


class Sim:
    def __init__(self):
        self.t = 0.0
        self.last_event_t = 0.0          # time of last processed event
        self.run_wall_s = 0.0            # real seconds inside run() loops
        self.run_cpu_s = 0.0             # process CPU seconds inside run()
        self._q: List[Tuple[float, int, Event]] = []
        self._seq = itertools.count()
        self._live = 0      # non-daemon events outstanding
        self.events_processed = 0

    def at(self, t: float, fn: Callable, note: str = "",
           daemon: bool = False, args: Tuple = ()):
        if not daemon:
            self._live += 1
        heapq.heappush(self._q, (t if t > self.t else self.t,
                                 next(self._seq),
                                 Event(fn, args, note, daemon)))

    def after(self, dt: float, fn: Callable, note: str = "",
              daemon: bool = False, args: Tuple = ()):
        self.at(self.t + (dt if dt > 0.0 else 0.0), fn, note,
                daemon=daemon, args=args)

    def now(self) -> float:
        return self.t

    def run(self, until: Optional[float] = None, max_events: int = 10_000_000):
        """Process events until only daemon events remain (informer
        resyncs, metric samplers) or the horizon is reached.  On exit
        the clock stands at ``until`` (when given) even if the queue
        drained first; ``last_event_t`` keeps the drain time."""
        n = 0
        wall0 = time.perf_counter()
        cpu0 = time.process_time()
        q = self._q
        pop = heapq.heappop
        try:
            with tracing.span("sim.run"):
                while self._live > 0 and q:
                    if until is not None and q[0][0] > until:
                        break               # the head stays for a later run
                    t, _, ev = pop(q)
                    self.t = self.last_event_t = t
                    if not ev.daemon:
                        self._live -= 1
                    ev.fn(*ev.args)
                    n += 1
                    if n >= max_events:
                        notes = [e.note for _, _, e in heapq.nsmallest(8, q)
                                 if e.note]
                        raise RuntimeError(
                            f"sim exceeded {max_events} events — likely a "
                            f"polling loop never terminated; next pending "
                            f"notes: {notes if notes else '(unnamed events)'}")
        finally:
            # counted however the loop ends, an exception unwinding it
            # included (a payload's error, a benchmark window closing).
            # Wall time of event processing only — ends with the last
            # processed event (the clock's last_event_t), so throughput
            # figures exclude setup before the loop and any epilogue
            # after it (benchmarks divide events by this, see
            # bench_scale)
            self.events_processed += n
            self.run_wall_s += time.perf_counter() - wall0
            self.run_cpu_s += time.process_time() - cpu0
        if until is not None and until > self.t:
            self.t = until

    def idle(self) -> bool:
        return self._live == 0


def measure_wall(fn: Callable[[], Any]) -> float:
    """Wall seconds of ``fn()``, including the device work it enqueued:
    a non-None result is blocked on (``jax.block_until_ready``) before
    the clock stops. jax is imported only then, so virtual payloads
    (which return None) keep the control plane jax-free."""
    t0 = time.perf_counter()
    out = fn()
    if out is not None:
        import jax
        jax.block_until_ready(out)
    return time.perf_counter() - t0
