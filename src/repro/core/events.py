"""Event-trigger mechanism (§4.6): registry + callbacks.

The registry ties KubeAdaptor's modules together: informer handlers
emit events ('pod-succeeded', 'pod-deleted', ...), registered callbacks
respond in the same virtual instant — the quick create/destroy switch
the paper credits for its resource-usage advantage.

Same-instant dispatches coalesce: the first emit of an instant opens a
dispatch buffer and schedules one flush; later emits at that instant
append.  The flush fires the callbacks in exact emit order, passing
their positional args through (no per-callback event or lambda).
Callbacks scheduled between two emits of one instant can only target
later times, so nothing can interleave with the batch.  Emits issued
*during* a flush open a fresh buffer, queuing behind the current event.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

from repro.core.sim import Sim


class EventRegistry:
    def __init__(self, sim: Sim):
        self.sim = sim
        self._subs: Dict[str, List[Callable]] = defaultdict(list)
        self.emitted: Dict[str, int] = defaultdict(int)
        # open same-instant dispatch batch: (instant, [(cb, args), ...])
        self._buf: Optional[Tuple[float, List[Tuple[Callable, tuple]]]] = None

    def register(self, name: str, cb: Callable):
        self._subs[name].append(cb)

    def emit(self, name: str, *args):
        self.emitted[name] += 1
        subs = self._subs[name]
        if not subs:
            return
        now = self.sim.t
        buf = self._buf
        if buf is not None and buf[0] == now:
            pending = buf[1]
        else:
            pending = []
            self._buf = (now, pending)
            self.sim.after(0.0, self._flush, note="event-dispatch",
                           args=(now, pending))
        for cb in subs:
            pending.append((cb, args))

    def _flush(self, due: float, pending: List[Tuple[Callable, tuple]]):
        buf = self._buf
        if buf is not None and buf[0] == due and buf[1] is pending:
            self._buf = None        # emits during the flush re-arm
        for cb, args in pending:
            cb(*args)
