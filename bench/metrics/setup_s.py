"""Process start to the first request of the window: loading, weights
from the seed, conversion, compilation or cache loads, warm-up (s)."""


def read(run):
    return run.setup_s
