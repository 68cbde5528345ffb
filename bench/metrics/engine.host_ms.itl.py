"""Host time per engine step in which the device had nothing to run, in
ms (readers.host_ms); it lengthens every gap between tokens."""
from bench import readers


def read(ctx):
    return readers.host_ms(ctx)
