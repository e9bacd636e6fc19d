"""Scheduler: median over the ticks in the window that hold a decode step
of the device-idle ms inside the program's `scheduler.tick` span but
outside its `engine.step` and `engine.admit` (traced runs, spans on)."""
from bench import spans


def read(run):
    got = run.program_spans
    return None if got is None else spans.tick_idle_ms(got["instances"])
