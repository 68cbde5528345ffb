"""Device time per decode run under the ``paged_gather`` scope (the
block tables' pages gathered into per-slot K/V, every layer), in ms;
with every slot decoding, the tokens a second are the slots over the
step."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "decode", r"/paged_gather/")
