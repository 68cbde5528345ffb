"""Useful work of the routed experts' grouped GEMMs (scope ``moe/experts``
of both serving programs), counted from the configuration's shapes and
the tokens each call routes: a decode call's active slots, a prefill
call's valid prompt tokens. Each token runs k experts' gated MLPs (three
d x f matrices); padded capacity rows are not work.

Bytes count what has to cross HBM at least once: the weights of every
expert a call can reach, min(E, tokens x k), and the routed rows in and
out.
"""
from __future__ import annotations

import re
from typing import Tuple

from bench import kernels, readers, work

EXPERTS = r"/moe/(?:checkpoint/)?experts/"
ROUTING = r"/moe/(?:checkpoint/)?(?:router|dispatch|combine)/"


def expert_gemm_work(spec: dict, tokens: int) -> Tuple[float, float]:
    """(flops, bytes) of one call's expert GEMMs over all layers."""
    d, f = spec["hidden_size"], spec["intermediate_size"]
    e, k = spec["num_local_experts"], spec["num_experts_per_tok"]
    rows = tokens * k
    flops = 2.0 * rows * 3 * d * f
    nbytes = (min(e, rows) * 3 * d * f + rows * 2 * d) * work.BF16
    layers = spec["num_hidden_layers"]
    return layers * flops, float(layers * nbytes)


def routed_tokens(kind: str, host) -> int:
    """Tokens a call routes, from the host's log of the call
    (``harness.Calls``)."""
    return len(host) if kind == "decode" else int(host[1])


def expert_gemm_roofline(ctx):
    """The least time the chip needs for the expert GEMMs of every decode
    and prefill run in the traced window, over their device time under
    ``moe/experts``, in %; None where the programs carry no such scope."""
    if ctx.peaks is None:
        return None
    least = spent = 0.0
    for kind in ("decode", "prefill"):
        prog = ctx.programs.get(kind)
        pairs = readers.runs(ctx, kind)
        if prog is None or not pairs:
            continue
        rx = re.compile(EXPERTS)
        for run, host in pairs:
            spent += sum(o.dur for o in run.ops if rx.search(
                prog.op_name.get(kernels.op_name(o), "")))
            least += work.roofline_seconds(
                *expert_gemm_work(ctx.spec, routed_tokens(kind, host)),
                ctx.peaks)
    return readers.share(least, spent) if spent > 0 else None
