"""Useful model operations of the decode runs (the active slots only,
attention at each slot's real context) over their device time times
the chip's bf16 peak, in %."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx, "decode")
