"""Flash-decode Pallas kernel: one new token vs. a long KV cache shard.

Bandwidth-bound (the paper's Fig. 15 workload): the kernel's job is to
stream K/V tiles from HBM once at full bandwidth while maintaining the
online softmax. Emits BOTH the un-normalized-combinable output ``o`` and
the log-sum-exp ``lse`` so the *distributed* flash decode
(core/flash_decode.py) can merge partials from sequence-parallel KV shards
with the low-latency AllGather — exactly the paper's FlashDecode+AG.

One grid cell serves all ``Hq / Hkv`` query heads of a KV head, so each
K/V tile is read once per KV head. The per-slot lengths arrive by scalar
prefetch: tiles wholly past a slot's length are neither fetched (the
index map repeats the last live tile) nor computed. ``lse`` is written
lane-broadcast over 128 lanes to satisfy the TPU's (8, 128) tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128


def _decode_kernel(
    len_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    lse_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    bkv: int,
    kv_tiles: int,
):
    length = len_ref[pl.program_id(0)]
    ikv = pl.program_id(2)

    @pl.when(ikv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ikv * bkv < length)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, d) — the group's heads
        k = k_ref[0, 0].astype(jnp.float32)  # (bkv, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bkv)
        first = ikv * bkv
        s = jnp.where(
            first + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1) < length,
            s, NEG_INF)
        # rows past the length (or past the cache, in a ragged last tile)
        # may hold anything: zero them so p = 0 cannot meet a NaN
        v = jnp.where(
            first + jax.lax.broadcasted_iota(jnp.int32, (bkv, 1), 0) < length,
            v, 0.0)
        m_prev = m_ref[...]  # (G, LANES), lane-broadcast
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ikv == kv_tiles - 1)
    def _done():
        # a slot of length 0 (a sequence shard past the sequence's end)
        # computed nothing: o = 0 and lse = NEG_INF, a zero weight in
        # the distributed combine
        l_fin = jnp.where(l_ref[...] > 0, l_ref[...], 1.0)
        o_ref[0, 0] = acc_ref[...] / l_fin[:, :1]
        lse_ref[0, 0] = m_ref[...] + jnp.log(l_fin)


def flash_decode(
    q: jax.Array,  # (B, Hq, D)
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,
    length: jax.Array,  # (B,) int32 valid KV length
    *,
    scale: float | None = None,
    bkv: int = 512,
    interpret: bool = False,
):
    """Returns (o, lse): o (B, Hq, D) f32, lse (B, Hq) f32."""
    b, hq, d = q.shape
    _, hkv, s_len, _ = k.shape
    group = hq // hkv
    bkv = min(bkv, s_len)
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    kv_tiles = pl.cdiv(s_len, bkv)
    kernel = functools.partial(
        _decode_kernel, scale=scale, bkv=bkv, kv_tiles=kv_tiles
    )

    def kv_map(bb, h, ikv, lens):
        last = (jnp.maximum(lens[bb], 1) - 1) // bkv
        return (bb, h, jnp.minimum(ikv, last), 0)

    def head_map(bb, h, ikv, lens):
        return (bb, h, 0, 0)

    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, hkv, kv_tiles),
            in_specs=[
                pl.BlockSpec((1, 1, group, d), head_map),
                pl.BlockSpec((1, 1, bkv, d), kv_map),
                pl.BlockSpec((1, 1, bkv, d), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, group, d), head_map),
                pl.BlockSpec((1, 1, group, LANES), head_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((group, LANES), jnp.float32),
                pltpu.VMEM((group, LANES), jnp.float32),
                pltpu.VMEM((group, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(length.astype(jnp.int32), q.reshape(b, hkv, group, d), k, v)
    return o.reshape(b, hq, d), lse[..., 0].reshape(b, hq)
