"""Host ms per engine step waiting for the device: the program's
``jalad.sync`` spans under ``jalad.stream.step``, its joins left out."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.stream_sync_ms()
