"""Paper Table 4 — AllGather + MoE GroupGEMM (TP mode, ring overlap).

Reduced versions of the paper's AG+MoE-1/-5/-13 rows (tokens/rank, hidden
sizes scaled to CPU); derived column reports tokens/s and the paper-shape
v5e analytic overlap win for the token gather.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import moe_overlap as mo
from repro.core import tuner
from repro.kernels import ops

from .common import row, time_fn

# (paper row, tokens/rank, in_hidden, out_hidden(dff), experts, topk)
CASES = [
    ("AG+MoE-1", 64, 128, 96, 15, 4),
    ("AG+MoE-5", 64, 256, 128, 8, 2),
    ("AG+MoE-13", 128, 96, 128, 16, 6),
]


def rows():
    w = min(8, jax.device_count())
    mesh = jax.make_mesh((w,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    out = []
    for name, t_loc, d, dff, e, k in CASES:
        x = jnp.asarray(rng.randn(t_loc * w, d), jnp.float32)
        logits = jnp.asarray(rng.randn(t_loc * w, e), jnp.float32)
        wi = jnp.asarray(rng.randn(e, d, dff) / np.sqrt(d), jnp.float32)
        wo = jnp.asarray(rng.randn(e, dff, d) / np.sqrt(dff), jnp.float32)
        cap = max(8, t_loc * k // e * 2)

        def expert_fn(tok, lg):
            dsp, info = mo.dispatch(tok, *mo.topk_route(lg, k), e, cap)
            y = ops.grouped_matmul(dsp, wi, out_dtype=tok.dtype)
            y = jax.nn.silu(y)
            y = ops.grouped_matmul(y, wo, out_dtype=tok.dtype)
            return mo.topk_combine(y, info)

        def ag_moe_step(xl, ll, mode):
            return mo.ag_moe(xl, ll, expert_fn, "tp", mode=mode)

        for mode in ("ring", "one_shot"):
            f = jax.jit(jax.shard_map(
                functools.partial(ag_moe_step, mode=mode), mesh=mesh,
                in_specs=(P("tp", None), P("tp", None)),
                out_specs=P(None, None), check_vma=False))
            us = time_fn(f, x, logits)
            toks_per_s = t_loc * w / (us * 1e-6)
            # paper-scale analytic: token gather of 1024 x 14336 over 8 ranks
            choice = tuner.analytic_ag_matmul(1024, 14336, 4096 // 8, 8)
            out.append(row(f"ag_moe/{name}/{mode}", us,
                           f"tokens_per_s={toks_per_s:.0f}"
                           f";v5e_gather_mode={choice.mode}"))
    return out
