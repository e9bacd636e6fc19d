"""Process start to the first timed tick: weights, programs (compiled or
loaded from the persistent cache) and the warm-up through the served
path."""


def read(run):
    return run.setup_s
