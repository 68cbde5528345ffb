"""Device time under the program's named scopes. The paged programs
mark their parts with ``jax.named_scope`` (``repro.obs.scope``); the
scope path lands in each instruction's ``op_name`` metadata, which
``bench.kernels.Program`` keeps for every compiled program."""
from __future__ import annotations

import re

from bench import kernels

# the layer loop's own operations: under the ``layers`` scope and outside
# its body's call (slicing each layer's weights and pools out of the
# stacked leaves, writing the pools back)
LAYER_LOOP = r"/layers/(?!.*closed_call)"


def scope_ms(ctx, kind: str, pattern: str):
    """Device time per run of the ``kind`` program of the operations whose
    ``op_name`` ``pattern`` matches, in ms; None where none matches (a
    program built without the scopes)."""
    prog = ctx.programs.get(kind)
    if ctx.trace is None or prog is None:
        return None
    got = [r for r in ctx.trace.runs if r.kind == kind]
    rx = re.compile(pattern)
    picked = [o.dur for r in got for o in r.ops
              if rx.search(prog.op_name.get(kernels.op_name(o), ""))]
    return 1e3 * sum(picked) / len(got) if picked else None
