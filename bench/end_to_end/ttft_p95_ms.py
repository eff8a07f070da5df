"""95th percentile, over every request due in the window, of its first
token minus its scheduled arrival; a request with no token by the
window's end counts at the time it has waited."""
from bench import harness


def compute(rec, win, setup_s):
    return harness.percentile(harness.first_token_ms(rec, win), 95)
