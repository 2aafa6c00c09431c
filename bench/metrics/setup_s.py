"""setup_s: process start to the window's opening (host clock): imports,
weights, compile(), engine start and warm-up, from the persistent cache
after a cell's first run."""


def read(run):
    return run.setup_s
