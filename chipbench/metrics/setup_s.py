"""Process start to window open: weights, service build, warm-up of every
reachable program shape, and the warm span of the cell's own traffic."""


def read(run):
    return run.setup_s
