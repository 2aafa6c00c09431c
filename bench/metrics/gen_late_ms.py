"""gen_late_ms: 95th percentile of how late the load generator got each
open-loop request out (sent minus due, host clock) over the requests due
in the record's window, so that a starved generator is not read as a
fast server."""

from benchlib.record import percentile


def read(run):
    return percentile([1e3 * (s.t_sent - s.t_due) for s in run.sent
                       if run.t0 <= s.t_due <= run.t_end], 0.95)
