"""Host ms per join: head prefill, whole-sequence encode and decode,
tail prefill."""
from bench import readers


def read(run):
    return readers.mean_ms(run, "join")
