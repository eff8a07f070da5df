"""95th percentile of every gap between consecutive output tokens of one
request, both inside the window."""
from bench import harness


def compute(rec, win, setup_s):
    return harness.percentile(harness.token_gaps_ms(rec, win), 95)
