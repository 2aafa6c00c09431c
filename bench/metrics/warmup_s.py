"""warmup_s: engine start plus the warm-up of every dispatch size the window
uses (host clock): the compiled program's share of setup_s."""


def read(run):
    return run.warmup_s
