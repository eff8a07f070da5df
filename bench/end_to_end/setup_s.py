"""Seconds from process start to the window's start: TPU start, weights
from the seed, warm-up of every shape the traffic uses, compilation on a
cold cache."""


def compute(rec, win, setup_s):
    return setup_s
