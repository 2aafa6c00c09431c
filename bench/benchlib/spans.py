"""Host spans of the serving engine, recorded for the trace reduction.

The serving engines take a ``tracer=`` and call ``span(name, track)``
around packing, credit waits, dispatch and delivery.  This recorder keeps
each span as ``(name, thread, start, end)`` on ``time.perf_counter``;
``trace.py`` puts them on the profiler's clock through marker programs.
They are not written into the profiler trace as annotations: host events
are only recorded at ``host_tracer_level >= 1``, and at that level the
runtime's own per-chunk events of every host-to-device copy slowed the
served rate about fourfold.  Async begin/end pairs, counters and instants
are dropped: no metric reads them.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from contextlib import contextmanager


class HostSpans:
    enabled = True
    clock = staticmethod(time.perf_counter)

    def __init__(self, capacity: int = 1 << 20):
        self.spans: deque = deque(maxlen=capacity)

    @contextmanager
    def span(self, name: str, track: str = "dispatch", **args):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.spans.append((name, threading.current_thread().name, t0,
                               time.perf_counter()))

    def on_trace_clock(self, offset_ns: int, thread_suffix: str):
        """``(name, start_ns, end_ns)`` of the spans of threads whose name
        ends in ``thread_suffix``, shifted by ``offset_ns``."""
        return [(n, int(s * 1e9) + offset_ns, int(e * 1e9) + offset_ns)
                for n, th, s, e in list(self.spans)
                if th.endswith(thread_suffix)]

    def begin(self, name, track, event_id, **args) -> None:
        pass

    def end(self, name, track, event_id, **args) -> None:
        pass

    def counter(self, name, value, track: str = "dispatch") -> None:
        pass

    def instant(self, name, track: str = "dispatch", **args) -> None:
        pass
