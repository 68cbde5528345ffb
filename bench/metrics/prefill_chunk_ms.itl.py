"""Device time per run of the chunked-prefill program, in ms; a step
that carries a chunk makes every decoding slot wait for it."""
from bench import readers


def read(ctx):
    return readers.program_ms(ctx, "prefill")
