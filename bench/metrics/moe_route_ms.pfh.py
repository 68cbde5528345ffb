"""Device time per prefill run under the ``moe/router``, ``moe/dispatch``
and ``moe/combine`` scopes (softmax and top-k, the scatter into the
experts' capacity buffers, the weighted gather back; every layer), in
ms: the work a dropless grouped GEMM over the routed rows would
remove."""
from bench import moe_work, scopes


def read(ctx):
    return scopes.scope_ms(ctx, "prefill", moe_work.ROUTING)
