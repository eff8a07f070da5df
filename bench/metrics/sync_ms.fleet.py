"""Host ms per request waiting for the device: the program's ``jalad.sync``
spans under ``jalad.fleet.serve``."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.fleet_sync_ms()
