"""step_ms.sat: device-busy time (union of operation intervals, from the
profiler trace) per dispatched program, in milliseconds."""


def read(run):
    t = run.trace
    if not t or not t["dispatches"]:
        return None
    return 1e3 * t["busy_s"] / t["dispatches"]
