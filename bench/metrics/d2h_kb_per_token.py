"""Engine: KiB the engine copied from the device to the host over the
window (its `d2h_bytes` counter, read in traced runs) per output token
that reached the host in the window."""
from bench import spans


def read(run):
    got = run.engine_counters
    return None if got is None else spans.d2h_kb_per_token(
        got.get("d2h_bytes"), run.tokens_in_window())
