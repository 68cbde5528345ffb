"""The flash-decode kernel's share of its roofline in the decode runs,
in % (readers.flash_decode_roofline); silent where the decode program
holds no such kernel, and decode_mfu.tok still bounds a gain."""
from bench import readers


def read(ctx):
    return readers.flash_decode_roofline(ctx)
