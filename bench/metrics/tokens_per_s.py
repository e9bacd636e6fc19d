"""Output tokens that reached the host in the window, per second."""


def read(run):
    return run.tokens_in_window() / run.seconds
