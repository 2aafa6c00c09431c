"""result_wait_ms: median, over the requests answered inside the traced
window, of the time from the launch of the program that carries the
request's last row to its logits on the host (the engine's ``t_done -
t_launch``, on its clock): the device queue behind earlier programs, the
execution and the read back.  None where the program stamps no launch."""

from benchlib.record import percentile


def read(run):
    reqs = [s.request for s in run.sent if run.in_window(s)]
    return percentile([1e3 * (r.t_done - r.t_launch) for r in reqs
                       if getattr(r, "t_launch", None) is not None], 0.50)
