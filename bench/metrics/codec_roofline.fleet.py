"""Codec share of its memory roofline at the fleet boundaries."""
from bench import readers


def read(run):
    return readers.roofline(run, ("encode", "decode"), "codec_bytes")
