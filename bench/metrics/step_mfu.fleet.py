"""Model FLOPs of the images served, over the host time inside
FleetServer.serve, over the bf16 peak (the model computes in float32;
the chip publishes no float32 peak)."""
from bench import readers


def read(run):
    return readers.mfu(run, "serve")
