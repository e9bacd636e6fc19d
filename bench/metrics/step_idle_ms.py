"""Engine: median over the decode steps in the window of the device-idle
ms inside the program's `engine.step` span (traced runs, spans on)."""
from bench import spans


def read(run):
    got = run.program_spans
    return None if got is None else spans.step_idle_ms(got["instances"])
