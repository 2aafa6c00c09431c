"""launch_wait_ms: median, over the requests answered inside the traced
window, of the time from submit() to the launch of the program that
carries the request's last row (the engine's ``t_launch - t_submit``, on
its clock): the queue, packing, the buffer fill, the credit wait, the
host-to-device copy and the launch.  None where the program stamps no
launch."""

from benchlib.record import percentile


def read(run):
    reqs = [s.request for s in run.sent if run.in_window(s)]
    return percentile([1e3 * (r.t_launch - r.t_submit) for r in reqs
                       if getattr(r, "t_launch", None) is not None], 0.50)
