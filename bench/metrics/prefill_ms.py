"""Median wall time of the engine's `admit` calls in the window (the call
ends on the host)."""
from bench.harness import percentile


def read(run):
    v = percentile(run.span_seconds("admit"), 50)
    return None if v is None else v * 1e3
