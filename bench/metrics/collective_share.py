"""collective_share: share of the traced window in which a collective
(collective-permute, all-reduce, ...) runs on a device and no compute does,
averaged over the chips, in percent."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0 or not t["collective_s"]:
        return None
    return 100.0 * t["collective_only_s"] / t["window_s"]
