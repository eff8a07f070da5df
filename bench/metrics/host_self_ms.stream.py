"""Host ms per engine step of the program's own work: ``jalad.stream.step``
outside its ``jalad.sync`` spans and its joins."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.stream_host_self_ms()
