"""Mean live slots an engine step advances: the ``slots`` stat of
``jalad.stream.head``."""
from bench import program_spans


def read(run):
    spans = program_spans.of_run(run)
    return None if spans is None else spans.live_slots()
