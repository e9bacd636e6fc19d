"""The decode step's model FLOPs (bench/counts.py) over the device time
of its program in the trace, over the peak bf16 FLOP/s: the whole
step's share of the peak."""
from bench import counts
from bench.harness import decode_device


def read(run):
    got = decode_device(run)
    if got is None:
        return None
    steps, seconds = got
    peak = counts.peaks(run.device_kind)
    flops = sum(counts.decode_step(run.cfg, lengths)["flops"]
                for lengths in steps)
    return 100.0 * flops / seconds / peak["bf16_flop_per_s"]
