"""Paper Table 5 — MoE GroupGEMM + ReduceScatter (ring accumulator)."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import moe_overlap as mo
from repro.kernels import ops

from .common import row, time_fn

# (paper row, tokens/rank, in_hidden, out_hidden, experts, topk)
CASES = [
    ("MoE-RS-1", 128, 96, 128, 8, 2),
    ("MoE-RS-4", 128, 96, 128, 16, 5),
    ("MoE-RS-6", 128, 128, 256, 8, 2),
]


def rows():
    w = min(8, jax.device_count())
    mesh = jax.make_mesh((w,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    out = []
    for name, t_loc, d, dff, e, k in CASES:
        t = t_loc * w
        x = jnp.asarray(rng.randn(t, d), jnp.float32)
        logits = jnp.asarray(rng.randn(t, e), jnp.float32)
        wi = jnp.asarray(rng.randn(e, d, dff) / np.sqrt(d), jnp.float32)
        wo = jnp.asarray(rng.randn(e, dff, d) / np.sqrt(dff), jnp.float32)
        cap = max(8, t * k // e * 2)

        def expert_fn(tok, lg):
            dsp, info = mo.dispatch(tok, *mo.topk_route(lg, k), e, cap)
            y = ops.grouped_matmul(dsp, wi, out_dtype=tok.dtype)
            y = jax.nn.silu(y)
            y = ops.grouped_matmul(y, wo, out_dtype=tok.dtype)
            return mo.topk_combine(y, info)

        def step(xf, lf):
            return mo.moe_rs(xf, lf, expert_fn, "tp")

        f = jax.jit(jax.shard_map(step, mesh=mesh,
                                  in_specs=(P(None, None), P(None, None)),
                                  out_specs=P("tp", None), check_vma=False))
        us = time_fn(f, x, logits)
        out.append(row(f"moe_rs/{name}/ring", us,
                       f"tokens_per_s={t / (us * 1e-6):.0f}"))
    return out
