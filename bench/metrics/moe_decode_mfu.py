"""The decode steps' model FLOPs (attention, router, head, and each
token-expert pair the engine's `moe_pairs` counted over the window) over
the device time of their program in the trace, over the peak bf16
FLOP/s. None without the counters."""
from bench import arch, counts
from bench.harness import decode_device


def read(run):
    got, n = decode_device(run), run.engine_counters
    if got is None or not n or "moe_pairs" not in n:
        return None
    steps, seconds = got
    work = arch.of(run.cfg).decode_window(
        run.cfg, steps, n["moe_experts_reached"], n["moe_pairs"])
    return 100.0 * work["flops"] / seconds \
        / counts.peaks(run.device_kind)["bf16_flop_per_s"]
