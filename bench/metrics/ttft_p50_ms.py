"""Median time from each first turn's scheduled arrival to its first
token on the host; a request still waiting at the close counts with its
age then."""
from bench.harness import percentile


def read(run):
    v = percentile(run.first_turn_waits("token"), 50)
    return None if v is None else v * 1e3
