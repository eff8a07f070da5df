"""95th percentile, over every request due in the window, of its result
ready (``block_until_ready``) minus its scheduled arrival; one unfinished
at the window's end counts at its elapsed time."""
from bench import harness


def compute(rec, win, setup_s):
    return harness.percentile(harness.request_latency_ms(rec, win), 95)
