"""p95 of the gaps between consecutive tokens of one turn, over all
turns."""
from bench.harness import percentile


def read(run):
    v = percentile(run.token_gaps(), 95)
    return None if v is None else v * 1e3
