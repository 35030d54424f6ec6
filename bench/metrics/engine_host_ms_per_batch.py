"""Host time per ``engine.search`` call: its wall time (the benchmark
wraps the call) less the step program's device time per launch."""
from bench.readout import step_program


def read(rec):
    step, calls = step_program(rec), rec["calls"]
    if step is None or not calls:
        return None
    wall_ms = sum(c["wall_s"] for c in calls) / len(calls) * 1e3
    return wall_ms - step[0] / step[1] * 1e3
