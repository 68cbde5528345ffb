"""Device time per decode run of the ``layers`` loop's own operations
(each layer's weights and pools sliced out of the stacked leaves, the
pools written back), outside the layer body, in ms; with every slot
decoding, the tokens a second are the slots over the step."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, "decode", scopes.LAYER_LOOP)
