"""Scheduler: p90 of the time from each first turn's scheduled arrival to
the start of its prefill (waiting at the close counts with its age)."""
from bench.harness import percentile


def read(run):
    v = percentile(run.first_turn_waits("admit"), 90)
    return None if v is None else v * 1e3
