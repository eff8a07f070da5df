"""Output tokens produced in the window, over the window."""
from bench import harness


def compute(rec, win, setup_s):
    return harness.tokens_in(rec, win) / win.seconds
