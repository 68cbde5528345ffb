"""Device time per run of the paged-decode program, in ms; every gap
between tokens holds one."""
from bench import readers


def read(ctx):
    return readers.program_ms(ctx, "decode")
