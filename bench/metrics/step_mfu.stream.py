"""Model FLOPs of the prefill and decode tokens processed, over the host
time inside engine steps, over the bf16 peak."""
from bench import readers


def read(run):
    return readers.mfu(run, "step")
