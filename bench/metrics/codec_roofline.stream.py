"""Codec share of its memory roofline: bytes the encodes and decodes
must move, at HBM bandwidth, over the device time of the programs they
launched, from the trace."""
from bench import readers


def read(run):
    return readers.roofline(run, ("encode", "decode"), "codec_bytes")
