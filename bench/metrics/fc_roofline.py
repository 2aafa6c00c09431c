"""fc_roofline: conv_roofline's quantity for the layers the streamed-matmul
kernels compute, over those kernels' device time, in percent."""


def read(run):
    t = run.trace
    if not t or not t["family_s"].get("fc"):
        return None
    least = run.least_seconds(t["dispatches"]).get("fc")
    if not least:
        return None
    return 100.0 * least / t["family_s"]["fc"]
