"""The decode steps' least time on the chip over the device time of
their program in the trace: the larger of their FLOPs over peak FLOP/s
and their needed bytes over peak HBM bytes/s, counted by the
configuration's architecture from each step's filled lengths and the
engine's expert counters over the window (`moe_experts_reached`,
`moe_pairs`). With the counters as window totals the bound is taken
over the window's sums, never above the sum of each step's bound, so
the share can only read low. None without the counters."""
from bench import arch, counts
from bench.harness import decode_device


def read(run):
    got, n = decode_device(run), run.engine_counters
    if got is None or not n or "moe_pairs" not in n:
        return None
    steps, seconds = got
    work = arch.of(run.cfg).decode_window(
        run.cfg, steps, n["moe_experts_reached"], n["moe_pairs"])
    return 100.0 * counts.least_seconds(
        work, counts.peaks(run.device_kind)) / seconds
