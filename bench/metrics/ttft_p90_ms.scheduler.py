"""Scheduler: p90 of the time from each first turn's scheduled arrival to
its first token on the host; a request still waiting at the close counts
with its age then. The tail of `ttft_p50_ms`: at this load it is set by
whether a long prompt's prefill meets another, so it swings with the
order of arrivals and is read per layer."""
from bench.harness import percentile


def read(run):
    v = percentile(run.first_turn_waits("token"), 90)
    return None if v is None else v * 1e3
