"""Share of the traced window in which no operation ran on the device,
in %."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx)
