"""Device time per prefill run under the ``chunk_attend`` scope (the
chunk's queries attending over the request's gathered pages, every
layer), in ms; a step that carries a chunk makes every decoding slot
wait for it."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "prefill", r"/chunk_attend/")
