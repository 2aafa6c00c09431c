"""latency_p50_ms: median, over every request of the window, of the time
from when it was due to when its answer arrived (host clock)."""

from benchlib.record import percentile


def read(run):
    return percentile(run.latencies_ms, 0.50)
