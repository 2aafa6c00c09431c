"""conv_roofline: the least time the chip could take for the work of the
layers the conv kernels compute in the traced window (benchlib/work.py:
each layer bound by its int8 operations or its minimal bytes), over the
device time of the conv kernels' events, in percent."""


def read(run):
    t = run.trace
    if not t or not t["family_s"].get("conv"):
        return None
    least = run.least_seconds(t["dispatches"]).get("conv")
    if not least:
        return None
    return 100.0 * least / t["family_s"]["conv"]
