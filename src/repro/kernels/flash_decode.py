"""Flash-decode Pallas kernels: one new token vs. a long KV cache.

Bandwidth-bound (the paper's Fig. 15 workload): the kernel's job is to
stream K/V from HBM once at full bandwidth while maintaining the online
softmax. Both kernels emit the un-normalized-combinable output ``o`` and
the log-sum-exp ``lse`` so the *distributed* flash decode
(core/flash_decode.py) can merge partials from sequence-parallel KV shards
with the low-latency AllGather — exactly the paper's FlashDecode+AG.
``lse`` is written lane-broadcast over 128 lanes to satisfy the TPU's
(8, 128) tiling.

:func:`flash_decode` reads a contiguous cache ``(B, Hkv, S, D)``: one
grid cell per KV head serves all ``Hq / Hkv`` query heads, so each K/V
tile is read once per KV head. The per-slot lengths arrive by scalar
prefetch: tiles wholly past a slot's length are neither fetched (the
index map repeats the last live tile) nor computed.

:func:`paged_flash_decode` reads a page pool in place through per-slot
block tables (serve/kvcache.py), copying each live page itself, from the
layer it is given of pools stacked over layers. The pool
stores a page lane-dense (:func:`page_rows`): with D = 64, one 128-lane
row holds two consecutive tokens, so each query head folds as two rows
of queries, one per lane half (``parts``), and the parts merge by their
log-sum-exp at the end.
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


def page_rows(page_size: int, head_dim: int):
    """How a page pool ``(num_pages, Hkv, rows, width)`` stores one KV
    head's page of ``page_size`` tokens: ``(rows, width)``, the tokens in
    order. A head narrower than a lane row packs ``128 // head_dim``
    consecutive tokens in each row. A pool whose last dimension is 64
    would be laid out by XLA with the pages in the lanes, so that a page
    is not contiguous in HBM; at 128 or more it is."""
    parts = LANES // head_dim if head_dim < LANES else 1
    if parts * head_dim != max(head_dim, LANES) or page_size % parts:
        parts = 1
    return page_size // parts, head_dim * parts


def _fold_tile(q, k, v, pos_s, pos_v, length, scale, m_ref, l_ref, acc_ref):
    """Fold one K/V tile into a group of query rows' online softmax (f32).

    q (R, w); k, v (t, w). ``pos_s`` (broadcastable to (R, t)) is the
    position of the token each score reads, ``pos_v`` (broadcastable to
    (t, w)) that of each value element. Entries at or past ``length``
    may hold anything (a ragged last tile, a scratch page): their keys
    and values are zeroed and their scores masked, so no NaN there meets
    a live score (a lane row may pair a live token with a dead one) or
    p = 0. m/l are (R, LANES), lane-broadcast; acc (R, w).
    """
    live = pos_v < length
    k = jnp.where(live, k, 0.0)
    v = jnp.where(live, v, 0.0)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (R, t)
    s = jnp.where(pos_s < length, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new


def _finish(m_ref, l_ref, acc_ref, parts: int = 1):
    """(o, lse) of G query heads folded as ``parts`` row groups of G,
    part p over lanes ``p*D .. (p+1)*D`` of acc: the parts merge by their
    log-sum-exp. A head that folded nothing (a slot of length 0, e.g. a
    sequence shard past the sequence's end) gives o = 0 and lse =
    NEG_INF, a zero weight in the distributed combine."""
    g = m_ref.shape[0] // parts
    d = acc_ref.shape[1] // parts
    ms = [m_ref[p * g:(p + 1) * g] for p in range(parts)]
    m = functools.reduce(jnp.maximum, ms)
    ws = [jnp.exp(mp - m) for mp in ms]
    l = sum(w * l_ref[p * g:(p + 1) * g] for p, w in enumerate(ws))
    acc = sum(w[:, :1] * acc_ref[p * g:(p + 1) * g, p * d:(p + 1) * d]
              for p, w in enumerate(ws))
    l_fin = jnp.where(l > 0, l, 1.0)
    return acc / l_fin[:, :1], m + jnp.log(l_fin)


def _init(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


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
    def _start():
        _init(m_ref, l_ref, acc_ref)

    @pl.when(ikv * bkv < length)
    def _step():
        first = ikv * bkv
        _fold_tile(q_ref[0, 0].astype(jnp.float32),
                   k_ref[0, 0].astype(jnp.float32),
                   v_ref[0, 0].astype(jnp.float32),
                   first + jax.lax.broadcasted_iota(jnp.int32, (1, bkv), 1),
                   first + jax.lax.broadcasted_iota(jnp.int32, (bkv, 1), 0),
                   length, scale, m_ref, l_ref, acc_ref)

    @pl.when(ikv == kv_tiles - 1)
    def _done():
        o_ref[0, 0], lse_ref[0, 0] = _finish(m_ref, l_ref, acc_ref)


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


def _paged_kernel(
    len_ref,
    table_ref,
    layer_ref,
    q_ref,
    pool_k,
    pool_v,
    o_ref,
    lse_ref,
    k_buf,
    v_buf,
    sems,
    buf_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    page_size: int,
    block_pages: int,
    pages_per_slot: int,
    parts: int,
):
    b = pl.program_id(0)
    hkv, rows, width = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
    group, d = rows // parts, width // parts
    t = block_pages * page_size // parts  # lane rows of a block, per head
    bkv = block_pages * page_size

    def live_pages(bb):
        return jnp.minimum(pl.cdiv(len_ref[bb], page_size), pages_per_slot)

    def copies(bb, j, buf, start: bool):
        """Start (or wait for) the copies of slot ``bb``'s block ``j``
        into buffer ``buf``: one DMA per live page of the layer, all KV
        heads at once (a page is contiguous across them)."""
        n = jnp.clip(live_pages(bb) - j * block_pages, 0, block_pages)

        def page(i, carry):
            src = table_ref[bb * pages_per_slot + j * block_pages + i]
            for pool, dst, kv in ((pool_k, k_buf, 0), (pool_v, v_buf, 1)):
                dma = pltpu.make_async_copy(pool.at[layer_ref[0], src],
                                            dst.at[buf, i], sems.at[kv, buf])
                dma.start() if start else dma.wait()
            return carry

        jax.lax.fori_loop(0, n, page, 0)

    @pl.when(b == 0)
    def _prime():
        buf_ref[0] = 0
        copies(0, 0, 0, start=True)

    _init(m_ref, l_ref, acc_ref)
    length = len_ref[b]
    blocks = jnp.maximum(pl.cdiv(live_pages(b), block_pages), 1)

    def part_of(idx, size):  # idx // size for idx < parts * size
        return sum((idx >= p * size).astype(jnp.int32)
                   for p in range(1, parts)) if parts > 1 else 0

    # token of score (r, c): parts * c + part of row r; of value (c, l):
    # parts * c + part of lane l
    tok_s = (parts * jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
             + part_of(jax.lax.broadcasted_iota(jnp.int32, (rows, t), 0),
                       group))
    tok_v = (parts * jax.lax.broadcasted_iota(jnp.int32, (t, width), 0)
             + part_of(jax.lax.broadcasted_iota(jnp.int32, (t, width), 1),
                       d))

    def block(j, buf):
        # prefetch the next block, the next slot's first after our last
        last = j + 1 == blocks
        nb = jnp.where(last, b + 1, b)

        @pl.when(nb < pl.num_programs(0))
        def _prefetch():
            copies(nb, jnp.where(last, 0, j + 1), 1 - buf, start=True)

        copies(b, j, buf, start=False)

        @pl.when(j * bkv < length)
        def _fold():
            first = j * bkv
            for h in range(hkv):
                k = k_buf[buf, :, h].astype(jnp.float32).reshape(t, width)
                v = v_buf[buf, :, h].astype(jnp.float32).reshape(t, width)
                _fold_tile(q_ref[0, h], k, v, first + tok_s, first + tok_v,
                           length, scale, m_ref.at[h], l_ref.at[h],
                           acc_ref.at[h])

        return 1 - buf

    buf_ref[0] = jax.lax.fori_loop(0, blocks, block, buf_ref[0])
    for h in range(hkv):
        o_ref[0, h], lse_ref[0, h] = _finish(m_ref.at[h], l_ref.at[h],
                                             acc_ref.at[h], parts)


def paged_flash_decode(
    q: jax.Array,  # (B, Hq, D)
    pool_k: jax.Array,  # (L, num_pages, Hkv, rows, width): page_rows
    pool_v: jax.Array,
    table: jax.Array,  # (B, P) int32 page ids
    length: jax.Array,  # (B,) int32 valid KV length
    layer: jax.Array,  # () int32: the layer of the stacked pools to read
    *,
    scale: float | None = None,
    bkv: int = 512,
    interpret: bool = False,
):
    """Flash decode that reads each slot's K/V pages in place, through its
    block-table row: no per-slot copy of the cache is built. Returns
    (o, lse) as :func:`flash_decode` does.

    One grid step per slot walks its live pages in blocks of about
    ``bkv`` tokens, double-buffered: the copies of the next block (the
    next slot's first, after a slot's last) run while this one is folded.
    Pages past ``ceil(length / page_size)`` are neither copied nor
    computed; table entries there may point anywhere in the pool.

    The pools are stacked over layers and read at ``layer``, so a layer
    loop that carries them reads each layer's pages where they lie.
    """
    b, hq, d = q.shape
    _, _, hkv, rows, width = pool_k.shape
    pages_per_slot = table.shape[1]
    group = hq // hkv
    parts = width // d
    page_size = rows * parts
    if width % LANES:
        raise ValueError(f"page rows of {width} lanes (head_dim {d}): a "
                         "DMA moves whole 128-lane tiles")
    block_pages = max(1, min(bkv // page_size, pages_per_slot))
    scale = scale if scale is not None else 1.0 / float(np.sqrt(d))
    kernel = functools.partial(
        _paged_kernel, scale=scale, page_size=page_size,
        block_pages=block_pages, pages_per_slot=pages_per_slot, parts=parts)
    # query head g of part p sits in row p * G + g at lanes p*D .. (p+1)*D
    qp = jnp.einsum("bhgd,pr->bhpgrd",
                    q.astype(jnp.float32).reshape(b, hkv, group, d),
                    jnp.eye(parts, dtype=jnp.float32))
    qp = qp.reshape(b, hkv, parts * group, width)

    def slot_map(bb, lens, table, layer):
        return (bb, 0, 0, 0)

    buf = pltpu.VMEM((2, block_pages, hkv, rows, width), pool_k.dtype)
    state = (hkv, parts * group)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, hkv, parts * group, width), slot_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((1, hkv, group, d), slot_map),
                pl.BlockSpec((1, hkv, group, LANES), slot_map),
            ],
            scratch_shapes=[
                buf,
                buf,
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM(state + (LANES,), jnp.float32),
                pltpu.VMEM(state + (LANES,), jnp.float32),
                pltpu.VMEM(state + (width,), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, group, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, group, LANES), jnp.float32),
        ],
        # the copies run across grid steps: the slots go in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(length.astype(jnp.int32), table.astype(jnp.int32).reshape(-1),
      jnp.asarray(layer, jnp.int32).reshape(1), qp, pool_k, pool_v)
    return o.reshape(b, hq, d), lse[..., 0].reshape(b, hq)
