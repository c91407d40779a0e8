"""Set-up seconds: process start to the first timed call (imports, weights
made from the seed, warm-up of the cell's shapes from the compile cache)."""


def read(run):
    return run.setup_s
