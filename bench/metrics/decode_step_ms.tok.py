"""Device time per run of the paged-decode program, in ms; with every
slot decoding, the tokens a second are the slots over the step."""
from bench import readers


def read(ctx):
    return readers.program_ms(ctx, "decode")
