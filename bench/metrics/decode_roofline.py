"""The decode step's least time on the chip (the larger of its FLOPs
over peak FLOP/s and its needed bytes over peak HBM bytes/s, from
bench/counts.py) over the device time of its program in the trace."""
from bench import counts
from bench.harness import decode_device


def read(run):
    got = decode_device(run)
    if got is None:
        return None
    steps, seconds = got
    peak = counts.peaks(run.device_kind)
    least = sum(counts.least_seconds(counts.decode_step(run.cfg, lengths),
                                     peak) for lengths in steps)
    return 100.0 * least / seconds
