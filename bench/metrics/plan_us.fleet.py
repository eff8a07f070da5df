"""Host microseconds per fleet re-plan (controller.current_plans)."""
from bench import readers


def read(run):
    ms = readers.mean_ms(run, "plan")
    return None if ms is None else ms * 1e3
