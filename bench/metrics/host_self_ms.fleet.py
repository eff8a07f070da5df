"""Host ms per request of the program's own work: ``jalad.fleet.serve``
outside its ``jalad.sync`` spans."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.fleet_host_self_ms()
