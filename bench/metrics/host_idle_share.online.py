"""host_idle_share.online: device idle time in the traced window while the
serving dispatcher was at host work (its innermost open span ``pack``,
``dispatch``, or a dispatch's ``fill``, ``h2d`` or ``launch``; not
``credit_wait``, not waiting for requests), from the trace's idle gaps,
over the window, in percent.  None where the dispatcher's spans do not
split a dispatch into fill, copy and launch."""

HOST_WORK = ("pack", "dispatch", "fill", "h2d", "launch")
DISPATCH_STEPS = ("fill", "h2d", "launch")


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    idle = dict(t["idle_gaps"])
    if not any(k in idle for k in DISPATCH_STEPS):
        return None
    return 100.0 * sum(idle.get(k, 0.0) for k in HOST_WORK) / t["window_s"]
