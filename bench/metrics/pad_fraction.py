"""pad_fraction: padded rows over all rows dispatched in the window, from
the serving engine's own counters, in percent."""


def read(run):
    rows = run.counter_delta("dispatched_rows")
    if rows <= 0:
        return None
    return 100.0 * run.counter_delta("padded_rows") / rows
