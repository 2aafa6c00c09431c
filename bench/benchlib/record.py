"""What one run saw: the record every metric reader reads.

A reader is ``bench/metrics/<name>.py`` with ``read(run) -> float | None``;
``None`` means the run has nothing for it to read, and the metric is
left out of the result line.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

from benchlib import work


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile: the ``ceil(p * n)``-th smallest."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(p * len(v)) - 1)]


@dataclass
class Run:
    seconds: float                 # t_end - t0
    t0: float                      # window open, host perf_counter
    t_end: float                   # window close; for a traced run, the
    #                                end of the traced part of the window
    chips: int
    setup_s: float
    warmup_s: float
    sent: List                     # traffic.Sent, every request of the window
    before: Dict[str, int]         # engine counters at window open
    after: Dict[str, int]          # ... and at window close
    peaks: Dict[str, float]
    layers: tuple                  # work.Layer rows of the configuration
    engines: Dict[str, str]        # layer -> the program's engine binding
    fc_engines: Sequence[str]      # engines that run on the fc kernels
    trace: Optional[Dict] = None   # trace.reduce(...) of a --trace 1 run

    def in_window(self, s) -> bool:
        t = s.t_done
        return t is not None and self.t0 <= t <= self.t_end

    @property
    def images_in_window(self) -> int:
        """Images of the requests answered inside the window."""
        return sum(s.n for s in self.sent if self.in_window(s))

    @property
    def latencies_ms(self) -> List[float]:
        """Due-to-answer time of every answered request of the run."""
        return [1e3 * (s.t_done - s.t_due) for s in self.sent
                if s.t_done is not None]

    def counter_delta(self, key: str) -> int:
        return self.after[key] - self.before[key]

    def least_seconds(self, dispatches: int) -> Dict[str, float]:
        """``work.least_seconds`` for the images answered in the window
        in ``dispatches`` dispatches, each layer under the kernel family
        that computes it."""
        return work.least_seconds(
            self.layers, self.images_in_window, dispatches,
            self.peaks["int8_ops_per_s"], self.peaks["hbm_bytes_per_s"],
            lambda l: work.kernel_family(l, self.engines[l.name],
                                         self.fc_engines))
