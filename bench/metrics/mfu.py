"""mfu: the whole step's share of the chips' int8 peak: 2 x the published
layers' multiply-accumulates for the images answered in the traced window,
over window x chips x peak int8 op/s (bench/peaks.json), in percent."""

from benchlib.work import macs_per_image


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    ops = 2 * macs_per_image(run.layers) * run.images_in_window
    if ops == 0:
        return None
    return 100.0 * ops / (t["window_s"] * run.chips
                          * run.peaks["int8_ops_per_s"])
