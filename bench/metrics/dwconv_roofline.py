"""dwconv_roofline: conv_roofline's quantity for the layers the depthwise
kernels compute (the ``dwconv`` family, which a configuration's own
``layers_of`` gives its depthwise rows), over those kernels' device time,
in percent."""


def read(run):
    t = run.trace
    if not t or not t["family_s"].get("dwconv"):
        return None
    least = run.least_seconds(t["dispatches"]).get("dwconv")
    if not least:
        return None
    return 100.0 * least / t["family_s"]["dwconv"]
