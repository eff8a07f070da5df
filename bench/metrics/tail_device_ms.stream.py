"""Device ms per engine step of the programs launched in
``jalad.stream.tail`` (the scatter, the vmapped tail decode and its masked
update)."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.stream_device_ms("tail")
