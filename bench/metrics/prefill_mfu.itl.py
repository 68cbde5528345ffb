"""Useful model operations of the prefill runs (valid prompt tokens
only, causal attention at their real positions, one row of logits)
over their device time times the chip's bf16 peak, in %."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx, "prefill")
