"""Device time of the served step's program per launch, from the
profiler trace of the window."""
from bench.readout import step_program


def read(rec):
    step = step_program(rec)
    return None if step is None else step[0] / step[1] * 1e3
