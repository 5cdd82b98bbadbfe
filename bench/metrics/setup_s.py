"""Process start to window open: imports, weights, frame pool, compiles
or cache loads and warm-up, and the pre-roll of traffic."""


def read(run):
    return float(run.setup_s)
