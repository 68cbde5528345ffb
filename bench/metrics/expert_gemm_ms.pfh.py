"""Device time per prefill run under the ``moe/experts`` scope (the
routed experts' grouped GEMMs and their activation, every layer), in ms;
a step that carries a chunk makes every decoding slot wait for it."""
from bench import moe_work, scopes


def read(ctx):
    return scopes.scope_ms(ctx, "prefill", moe_work.EXPERTS)
