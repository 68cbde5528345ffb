"""The shmem **tile executor**: every fused-kernel communication protocol,
written once, generic over a per-tile compute function.

The fused kernels used to hand-roll their put/signal step loops (ring +
credit flow control in ``ag_gemm``, the Alg. 3 push in ``rs_gemm``, the
Alg. 4 all-puts-up-front structure in ``ll_allgather``). Those protocols
are op-independent: what varies per op is only the *tile compute* — the
pure function applied to a chunk when it arrives (or before it is
pushed). This module factors the protocols out, so an overlapped kernel
is now a DECLARATION: ``executor.run(protocol, tile, operand, statics)``.

Protocols
---------
  ring_ag      Fig. 4 producer/consumer ring with credit flow control:
               the operand chunk rides rank -> rank+1 through a double-
               buffered symmetric workspace; a credit semaphore grants
               the left neighbor permission to overwrite a slot only
               after BOTH readers (local stage + outgoing remote DMA)
               are done. ``tile(chunk, *statics)`` consumes the chunk of
               step s (= rank (me - s) % W's data, the Fig. 7 swizzle);
               the result lands in that owner's output strip. The DMA of
               chunk s+1 is in flight while tile s computes.
  one_shot_ag  Alg. 4 low-latency structure: every rank one-sided-puts
               its chunk into every peer's slot ``me`` up-front (no
               serial ring dependency), waits for W arrivals, then runs
               ``tile`` per landed chunk. ``tile=None`` is the plain
               low-latency AllGather.
  push_rs      Alg. 3 push-mode GEMM+ReduceScatter: per step s the rank
               computes the partial tile for output block
               (me - s - 1) % W (peers first, own block last) and
               one-sided-pushes it to the owner's slot ``me``; each rank
               then waits for its W arrivals and locally reduces in f32.
               Compute of step s+1 overlaps the DMA of step s.
  one_shot_rs  the low-latency RS variant (ROADMAP): ALL W partials are
               computed first and the W puts issued up-front with
               distinct ring offsets — no compute/DMA interleaving
               dependency, latency-optimal for small blocks.
  one_shot_a2a the low-latency AllToAll (EP dispatch/combine): the
               operand's leading dim holds one block per destination PE;
               every PE pushes all its per-destination blocks up-front
               into the destination's slot ``me`` (signal-on-arrival),
               waits for its W arrivals, then runs ``tile`` per landed
               block — out[src] = tile(block PE src sent here). The
               inverse direction is the SAME protocol with the caller
               transposing block placement.
  bidir_ring_ag the executor-level form of the engine's bidir schedule:
               the chunk is split in half along dim 0; the top half
               rides the forward ring (me -> me+1), the bottom half the
               reverse ring (me -> me-1), each direction with its own
               double-buffered workspace + credit flow control, so each
               link direction carries half the bytes. Degrades to
               ring_ag when W < 3 or the chunk has odd rows (mirroring
               the graph lowering's degrade).
  ring_fold    carry-passing ring: the same double-buffered workspace +
               credit flow as ring_ag, but each arriving chunk is FOLDED
               into resident (f32) state instead of written to an output
               strip — the protocol behind ring attention's online
               softmax (m, l, acc) and any chunk-centric reduction that
               carries state across chunks. ``tile`` is a
               :class:`FoldTile` (init / fold / finalize), not a pure
               per-chunk function.
  two_level_ag two-axis (pod x ring) AllGather (Fig. 10): at each outer
               step the current region chunk is pushed over the slow
               inter-pod ring (double-buffered + credit flow) WHILE a
               pod-local one_shot exchange distributes it to every pod
               peer (per-source arrival signals); ``tile`` consumes all
               Wi chunks of the region per outer step. Takes
               ``axis=(inner, outer)`` and ``world=(Wi, Wo)``.
  two_level_rs two-axis GEMM+ReduceScatter (Fig. 10 / Alg. 5): per outer
               step the Wi partials for the scheduled pod region are
               computed and pushed up-front pod-locally (one_shot RS
               structure), reduced in f32, then the pod-reduced
               accumulator rides the inter-pod ring (peers' regions
               first, own pod last). Same two-axis calling convention.
  push_rs_ring_ag the chained boundary protocol (CoCoNet-style rs->ag
               fusion): an Alg. 3 push half reduces this rank's boundary
               block, a rank-local ``mid`` transforms it, and a Fig. 4
               ring half gathers the result — in ONE kernel with NO
               barrier between the halves. The ag ring's initial credit
               is granted before the rs half even starts, so a fast
               rank's first ag hop lands while slow ranks are still
               pushing/reducing rs partials: the boundary collective's
               exposed latency hides behind the rs tail. Each half owns
               its workspace/signals ("ws_rs"/"recv_rs" vs
               "ws_ag"/"recv_ag"/"cap_ag") so the overlapping halves
               never alias. ``tile`` is a :class:`ChainTile`.

Backends (``repro.shmem.default_backend``)
------------------------------------------
  pltpu     real TPU: a generic Pallas kernel per protocol (below);
            statics are staged to VMEM once, ``tile`` runs on VMEM
            values, communication is remote DMA + hardware semaphores.
  emulated  CPU / virtual devices: the SAME protocols against the
            host-side symmetric heaps of ``shmem.emulated`` — every
            put, arrival signal, credit and barrier runs with true
            concurrency semantics, validating the protocol logic
            without hardware.

Contract for ``tile``
---------------------
``tile(chunk, *statics) -> tile_value`` must be a pure jax function of
its inputs (it is traced inside the kernel). For the AG protocols the
output's leading dim defines the per-owner strip written into the
gathered output; for the RS protocols the output is the partial for one
output block (accumulated across ranks in f32).

``ring_fold`` instead takes a :class:`FoldTile` — three pure functions:
``init(chunk, *statics) -> state`` builds the resident (f32) state
pytree from shapes, ``fold(state, chunk, owner, *statics) -> state``
folds one arriving chunk (``owner`` is the traced global rank whose data
the chunk is), and ``finalize(state, *statics) -> out`` produces the
output once all W chunks have been folded.

Scale note (pltpu): refs are whole-shard (VMEM-resident per step). For
production shapes, wrap ``tile`` in ``pltpu.emit_pipeline`` tiling; the
signal protocols are unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import hw
from . import default_backend, tpu_backend
from . import emulated as em

Array = jax.Array

PROTOCOLS = ("ring_ag", "one_shot_ag", "push_rs", "one_shot_rs",
             "one_shot_a2a", "bidir_ring_ag", "ring_fold",
             "two_level_ag", "two_level_rs", "push_rs_ring_ag")

# Protocols that compose TWO mesh axes (pod x ring): axis=(inner, outer),
# world=(Wi, Wo); the linearized PE id is outer * Wi + inner.
TWO_LEVEL_PROTOCOLS = ("two_level_ag", "two_level_rs")


@dataclasses.dataclass(frozen=True)
class FoldTile:
    """A stateful fold tile for the carry-passing protocols.

    init      ``init(chunk, *statics) -> state`` — the resident (f32)
              state pytree, built from the chunk/static shapes (the
              chunk VALUE must not contribute: every chunk, own one
              included, is folded through ``fold``).
    fold      ``fold(state, chunk, owner, *statics) -> state`` — fold
              one arriving chunk; ``owner`` is the traced global rank
              whose data the chunk is (causal masks and swizzles key on
              it).
    finalize  ``finalize(state, *statics) -> out`` — the output once
              all W chunks are folded.
    live      optional ``live(owner, *statics) -> traced bool`` (or
              ``None`` for always-live): true iff folding ``owner``'s
              chunk does real work. A fold whose predicate is false must
              be a value no-op (the executor still calls it); protocols
              use the predicate to suppress the ``tile_compute`` span,
              so per-PE timelines show actual compute — the causal
              whole-block skip is the motivating case.
    """

    init: Callable
    fold: Callable
    finalize: Callable
    live: Optional[Callable] = None


@dataclasses.dataclass(frozen=True)
class ChainTile:
    """The compound tile of the chained boundary protocol
    (``push_rs_ring_ag``): an RS-side tile, a rank-local boundary
    function, and an AG-side tile. The protocol's single ``statics``
    tuple is split positionally — ``statics[:n_rs]`` feed ``rs``,
    ``statics[n_rs:n_rs + n_ag]`` feed ``ag``, the rest feed ``mid``.

    rs    ``rs(block, *rs_statics) -> partial`` — the producer GEMM's
          partial for one output block (reduced across ranks in f32).
    ag    ``ag(h_chunk, *ag_statics) -> strip`` — the consumer GEMM on
          one arriving boundary chunk; the result lands in the chunk
          owner's output strip.
    mid   ``mid(reduced, *mid_statics) -> h`` — rank-local ROW-WISE
          boundary function (residual add / norm / activation) applied
          to the owner's reduced block between the halves; ``None`` is
          the identity.
    """

    rs: Callable
    ag: Callable
    mid: Optional[Callable] = None
    n_rs: int = 0
    n_ag: int = 0


def _identity(x):
    return x


def _tile_struct(tile, chunk_struct, statics) -> jax.ShapeDtypeStruct:
    return jax.eval_shape(tile, chunk_struct, *statics)


def update_rows(out: Array, t: Array, row: int | Array) -> Array:
    """Write ``t`` into ``out`` at row offset ``row`` (all other dims full)."""
    return lax.dynamic_update_slice(out, t, (row,) + (0,) * (t.ndim - 1))


def slice_rows(x: Array, row, n: int) -> Array:
    """Slice ``n`` rows of ``x`` starting at ``row`` (all other dims full)."""
    return lax.dynamic_slice(x, (row,) + (0,) * (x.ndim - 1),
                             (n,) + x.shape[1:])


# ---------------------------------------------------------------------------
# Emulated backend: the protocols on host-side symmetric heaps
# ---------------------------------------------------------------------------


def _ring_ag_emulated(tile, chunk, statics, *, axis, world, out_dtype, cid):
    """Ring + credit protocol (Fig. 4): slot parity, 1 initial credit,
    grant-after-consume, and the skip of the final grants — the former
    ``_ag_gemm_emulated`` body, now op-independent."""
    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)
    ts = _tile_struct(tile, chunk, statics)
    tile_m = ts.shape[0]

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    # Initially my right neighbor's slot 1 is free: grant 1 credit.
    ctx.signal_op(left, sig="cap")

    cur = chunk
    out = jnp.zeros((tile_m * world,) + ts.shape[1:], out_dtype)
    for s in range(world):
        if s != world - 1:
            # producer: wait for a free slot at the right neighbor, then
            # putmem_signal my current chunk into their next slot.
            ctx.signal_wait_until(sig="cap", value=1)
            ctx.putmem_signal_nbi(cur, right, buf="ws", slot=(s + 1) % 2,
                                  sig="recv")
        # consumer: chunk of step s is rank (me - s)'s data.
        t = ctx.span("tile_compute", lambda c: tile(c, *statics), cur,
                     name=f"s{s}").astype(out_dtype)
        owner = lax.rem(me - s + world, world)
        out = update_rows(out, t, owner * tile_m)
        if s != world - 1:
            cur = ctx.wait_read(chunk.shape, chunk.dtype, buf="ws",
                                slot=(s + 1) % 2, sig="recv")
            # Slot fully consumed — only now may the left neighbor
            # overwrite it. Skip grants beyond the W-1 sends it makes.
            if s < world - 2:
                ctx.signal_op(left, sig="cap")
    ctx.barrier_all()
    return out


def _one_shot_ag_emulated(tile, chunk, statics, *, axis, world, out_dtype, cid):
    """Alg. 4 structure: broadcast_put my chunk into every PE's slot
    ``me`` (self included, so all W slots exist symmetrically), one
    signal_wait for all W arrivals, then tile each landed chunk."""
    ts = _tile_struct(tile, chunk, statics)
    tile_m = ts.shape[0]

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    ctx.broadcast_put(chunk, buf="ws", sig="recv")
    ctx.signal_wait_until(sig="recv", value=world)
    out = jnp.zeros((tile_m * world,) + ts.shape[1:], out_dtype)
    for r in range(world):
        shard = ctx.read_symmetric(chunk.shape, chunk.dtype, buf="ws", slot=r)
        t = ctx.span("tile_compute", lambda c: tile(c, *statics), shard,
                     name=f"r{r}").astype(out_dtype)
        out = update_rows(out, t, r * tile_m)
    ctx.barrier_all()
    return out


def _bidir_ring_ag_emulated(tile, chunk, statics, *, axis, world, out_dtype,
                            cid):
    """Bidirectional ring + credit protocol: two independent ring_ag
    instances (disjoint buffers/signals/credits in ONE context), the top
    chunk half riding me -> me+1 and the bottom half me -> me-1. The
    fold of step s overlaps BOTH directions' in-flight DMAs; each link
    direction carries half the bytes (the engine's bidir schedule,
    executor-level)."""
    m = chunk.shape[0]
    if world < 3 or m % 2:
        # mirror the graph lowering: bidir degenerates to ring
        return _ring_ag_emulated(tile, chunk, statics, axis=axis, world=world,
                                 out_dtype=out_dtype, cid=cid)
    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)
    half = m // 2
    cur_f, cur_b = chunk[:half], chunk[half:]
    ts = _tile_struct(tile, cur_f, statics)
    tile_h = ts.shape[0]
    tile_m = 2 * tile_h

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    # one initial credit per direction: fwd receives from the left ring
    # neighbor, bwd from the right (grant = "your next slot here is free")
    ctx.signal_op(left, sig="cap_f")
    ctx.signal_op(right, sig="cap_b")

    out = jnp.zeros((tile_m * world,) + ts.shape[1:], out_dtype)
    for s in range(world):
        if s != world - 1:
            ctx.signal_wait_until(sig="cap_f", value=1)
            ctx.putmem_signal_nbi(cur_f, right, buf="wsf", slot=(s + 1) % 2,
                                  sig="recv_f")
            ctx.signal_wait_until(sig="cap_b", value=1)
            ctx.putmem_signal_nbi(cur_b, left, buf="wsb", slot=(s + 1) % 2,
                                  sig="recv_b")
        # forward half: owner (me - s); backward half: owner (me + s)
        t_f = ctx.span("tile_compute", lambda c: tile(c, *statics), cur_f,
                       name=f"s{s}f").astype(out_dtype)
        out = update_rows(out, t_f, lax.rem(me - s + world, world) * tile_m)
        t_b = ctx.span("tile_compute", lambda c: tile(c, *statics), cur_b,
                       name=f"s{s}b").astype(out_dtype)
        out = update_rows(out, t_b,
                          lax.rem(me + s, world) * tile_m + tile_h)
        if s != world - 1:
            cur_f = ctx.wait_read(cur_f.shape, chunk.dtype, buf="wsf",
                                  slot=(s + 1) % 2, sig="recv_f")
            cur_b = ctx.wait_read(cur_b.shape, chunk.dtype, buf="wsb",
                                  slot=(s + 1) % 2, sig="recv_b")
            if s < world - 2:
                ctx.signal_op(left, sig="cap_f")
                ctx.signal_op(right, sig="cap_b")
    ctx.barrier_all()
    return out


def _one_shot_a2a_emulated(tile, xs, statics, *, axis, world, out_dtype, cid):
    """Low-latency AllToAll: all W per-destination blocks pushed up-front
    (self included, so every slot lands symmetrically) into slot ``me``
    of each destination, one signal_wait for the W arrivals, then tile
    each landed block into out[src]."""
    assert xs.shape[0] == world, (xs.shape, world)
    me = lax.axis_index(axis)
    blk_struct = jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype)
    ts = _tile_struct(tile, blk_struct, statics)

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    for off in range(world):  # all puts up-front, no waits between
        tgt = lax.rem(me + off, world)
        block = lax.dynamic_index_in_dim(xs, tgt, 0, keepdims=False)
        ctx.putmem_signal_nbi(block, tgt, buf="ws", slot=me, sig="recv")
    ctx.signal_wait_until(sig="recv", value=world)
    out = jnp.zeros((world,) + ts.shape, out_dtype)
    for src in range(world):
        block = ctx.read_symmetric(xs.shape[1:], xs.dtype, buf="ws", slot=src)
        t = ctx.span("tile_compute", lambda b: tile(b, *statics), block,
                     name=f"src{src}").astype(out_dtype)
        out = lax.dynamic_update_slice(out, t[None],
                                       (src,) + (0,) * len(ts.shape))
    ctx.barrier_all()
    return out


def _block(operand, blk, m_blk):
    return slice_rows(operand, blk * m_blk, m_blk)


def _rs_reduce(ctx, ts, world, out_dtype, decode=None):
    """signal_wait for all W partials, then the local f32 reduction.

    With a wire ``decode`` hook the landed partials are packed wire
    buffers (``ts`` describes the packed uint8 layout); each is decoded
    to f32 before accumulation."""
    ctx.signal_wait_until(sig="recv", value=world)
    acc_shape = ts.shape if decode is None else jax.eval_shape(decode, ts).shape
    acc = jnp.zeros(acc_shape, jnp.float32)
    for r in range(world):
        read_dtype = out_dtype if decode is None else ts.dtype
        part = ctx.read_symmetric(ts.shape, read_dtype, buf="ws", slot=r)
        if decode is None:
            acc = acc + part.astype(jnp.float32)
        else:
            acc = acc + ctx.span("decode", decode, part, name=f"r{r}")
    ctx.barrier_all()
    return acc.astype(out_dtype)


def _push_rs_emulated(tile, operand, statics, *, axis, world, out_dtype, cid,
                      decode=None):
    """Alg. 3 push protocol: per-step put of the partial into the owner's
    slot ``me`` (own block pushed to self at the last step, so all W
    slots land symmetrically), then one signal_wait + f32 reduction.

    Under a wire dtype the tile already returns the packed wire buffer
    (pushed verbatim — no out_dtype cast, which would corrupt the bytes)
    and ``decode`` unpacks each landed partial for the f32 reduction."""
    me = lax.axis_index(axis)
    m_blk = operand.shape[0] // world
    ts = _tile_struct(tile, _block(operand, 0, m_blk), statics)

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    for s in range(world):
        # Alg. 3 swizzle: peers' blocks first, own block last (blk == me)
        blk = lax.rem(me - s - 1 + 2 * world, world)
        partial = ctx.span("tile_compute", lambda b: tile(b, *statics),
                           _block(operand, blk, m_blk), name=f"s{s}")
        if decode is None:
            partial = partial.astype(out_dtype)
        ctx.putmem_signal_nbi(partial, blk, buf="ws", slot=me, sig="recv")
    return _rs_reduce(ctx, ts, world, out_dtype, decode)


def _one_shot_rs_emulated(tile, operand, statics, *, axis, world, out_dtype, cid,
                          decode=None):
    """Low-latency RS: ALL W partials computed first, then the W puts
    issued up-front at distinct ring offsets (own block first) — no
    serial compute/DMA dependency chain."""
    me = lax.axis_index(axis)
    m_blk = operand.shape[0] // world
    ts = _tile_struct(tile, _block(operand, 0, m_blk), statics)

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    partials = []
    for off in range(world):
        tgt = lax.rem(me + off, world)
        partial = ctx.span("tile_compute", lambda b: tile(b, *statics),
                           _block(operand, tgt, m_blk), name=f"off{off}")
        if decode is None:
            partial = partial.astype(out_dtype)
        partials.append((tgt, partial))
    for tgt, partial in partials:  # all puts up-front, no waits between
        ctx.putmem_signal_nbi(partial, tgt, buf="ws", slot=me, sig="recv")
    return _rs_reduce(ctx, ts, world, out_dtype, decode)


def _ring_fold_emulated(fold, chunk, statics, *, axis, world, out_dtype, cid):
    """Carry-passing ring: ring_ag's slot parity / 1 initial credit /
    grant-after-consume communication, but each arriving chunk is folded
    into resident f32 state instead of written to an output strip."""
    assert isinstance(fold, FoldTile), fold
    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    # Initially my right neighbor's slot 1 is free: grant 1 credit.
    if world > 1:
        ctx.signal_op(left, sig="cap")

    cur = chunk
    state = fold.init(chunk, *statics)
    for s in range(world):
        if s != world - 1:
            # producer: wait for a free slot at the right neighbor, then
            # putmem_signal my current chunk into their next slot.
            ctx.signal_wait_until(sig="cap", value=1)
            ctx.putmem_signal_nbi(cur, right, buf="ws", slot=(s + 1) % 2,
                                  sig="recv")
        # consumer: chunk of step s is rank (me - s)'s data — fold it
        # into the resident state while the next chunk's DMA is in flight.
        owner = lax.rem(me - s + world, world)
        # sync=True: the carry means step s+1 consumes this state anyway,
        # so the true-dependency end mark costs no overlap — and per-PE
        # tile_compute spans become honest compute time (the causal
        # load-balance pin in tests/test_placement_trace.py reads them)
        # when=live: a dynamically no-op fold (fully-masked causal
        # block) leaves no span, instead of a phantom one.
        alive = None if fold.live is None else fold.live(owner, *statics)
        state = ctx.span(
            "tile_compute", lambda st, c: fold.fold(st, c, owner, *statics),
            state, cur, name=f"s{s}", sync=True, when=alive)
        if s != world - 1:
            cur = ctx.wait_read(chunk.shape, chunk.dtype, buf="ws",
                                slot=(s + 1) % 2, sig="recv")
            if s < world - 2:
                ctx.signal_op(left, sig="cap")
    ctx.barrier_all()
    return ctx.span("tile_compute",
                    lambda st: fold.finalize(st, *statics),
                    state, name="finalize", sync=True).astype(out_dtype)


def _two_level_pe(axis, world):
    """((inner, outer), (Wi, Wo)) -> pod/ring coords + outer-ring peers
    (linearized pe = oid * Wi + iid; the outer ring preserves iid)."""
    inner, outer = axis
    wi, wo = world
    iid = lax.axis_index(inner)
    oid = lax.axis_index(outer)
    left = lax.rem(oid + wo - 1, wo) * wi + iid
    right = lax.rem(oid + 1, wo) * wi + iid
    return iid, oid, left, right


def _two_level_ag_emulated(tile, chunk, statics, *, axis, world, out_dtype,
                           cid):
    """Two-axis AG (Fig. 10): the current region chunk rides the slow
    inter-pod ring (double-buffered "ows" workspace + credit flow,
    exactly ring_ag's protocol over pods) while a pod-local one_shot
    exchange ("pws", per-source arrival signals, slot parity) hands it
    to every pod peer; the tile consumes all Wi region chunks per outer
    step. The inter-pod hop of region so+1 overlaps region so's pod
    exchange + compute."""
    wi, wo = world
    w_all = wi * wo
    iid, oid, left, right = _two_level_pe(axis, world)
    ts = _tile_struct(tile, chunk, statics)
    tile_m = ts.shape[0]

    ctx = em.ShmemCtx((axis[1], axis[0]), w_all, cid)  # pe = oid * wi + iid
    ctx.barrier_all()
    # outer ring: my left-pod peer's first send may land immediately
    if wo > 1:
        ctx.signal_op(left, sig="cap")

    cur = chunk
    out = jnp.zeros((tile_m * w_all,) + ts.shape[1:], out_dtype)
    for so in range(wo):
        region = lax.rem(oid - so + wo, wo)
        if so != wo - 1:
            # slow-link hop of the NEXT region overlaps this region's
            # pod-local exchange and compute (ring_ag credits over pods)
            ctx.signal_wait_until(sig="cap", value=1)
            ctx.putmem_signal_nbi(cur, right, buf="ows", slot=(so + 1) % 2,
                                  sig="orecv")
        # pod-local one_shot: all Wi puts up-front (self included, so the
        # slots land symmetrically). The arrival signal carries the
        # sender's ring OFFSET from the destination — a per-source
        # signal, so a pod peer racing one step ahead can never satisfy
        # this step's wait for a straggler's chunk (slot parity keeps
        # the two in-flight steps' data apart).
        for off in range(wi):
            tgt = oid * wi + lax.rem(iid + off, wi)
            ctx.putmem_signal_nbi(cur, tgt, buf="pws",
                                  slot=(so % 2) * wi + iid,
                                  sig=f"prcv{off}")
        for d in range(wi):
            ctx.signal_wait_until(sig=f"prcv{d}", value=1)
            src = lax.rem(iid - d + wi, wi)
            shard = ctx.read_symmetric(chunk.shape, chunk.dtype, buf="pws",
                                       slot=(so % 2) * wi + src)
            owner = region * wi + src
            t = ctx.span("tile_compute", lambda c: tile(c, *statics),
                         shard, name=f"o{so}d{d}").astype(out_dtype)
            out = update_rows(out, t, owner * tile_m)
        if so != wo - 1:
            cur = ctx.wait_read(chunk.shape, chunk.dtype, buf="ows",
                                slot=(so + 1) % 2, sig="orecv")
            if so < wo - 2:
                ctx.signal_op(left, sig="cap")
    ctx.barrier_all()
    return out


def _two_level_rs_emulated(tile, operand, statics, *, axis, world, out_dtype,
                           cid):
    """Two-axis RS (Fig. 10 / Alg. 5): per outer step (pod regions
    peers-first, own pod last) the Wi partials for the region's blocks
    are computed and pushed up-front pod-locally (one_shot RS structure,
    per-source signals), reduced in f32, then the pod-reduced
    accumulator rides the inter-pod ring — the slow-link transfer
    overlaps the next region's Wi computes."""
    wi, wo = world
    w_all = wi * wo
    iid, oid, left, right = _two_level_pe(axis, world)
    m_blk = operand.shape[0] // w_all
    ts = _tile_struct(tile, _block(operand, 0, m_blk), statics)

    ctx = em.ShmemCtx((axis[1], axis[0]), w_all, cid)  # pe = oid * wi + iid
    ctx.barrier_all()
    if wo > 1:
        ctx.signal_op(left, sig="cap")

    acc = None
    for so in range(wo):
        region = lax.rem(oid - so - 1 + 2 * wo, wo)
        # pod-local one_shot RS: all Wi partials computed and pushed
        # up-front (own inner block included, so slots land symmetrically)
        for off in range(wi):
            tgt_i = lax.rem(iid + off, wi)
            blk = region * wi + tgt_i
            partial = ctx.span(
                "tile_compute", lambda b: tile(b, *statics),
                _block(operand, blk, m_blk),
                name=f"o{so}off{off}").astype(jnp.float32)
            ctx.putmem_signal_nbi(partial, oid * wi + tgt_i, buf="pws",
                                  slot=(so % 2) * wi + iid,
                                  sig=f"prcv{off}")
        pod = jnp.zeros(ts.shape, jnp.float32)
        for d in range(wi):
            ctx.signal_wait_until(sig=f"prcv{d}", value=1)
            src = lax.rem(iid - d + wi, wi)
            part = ctx.read_symmetric(ts.shape, jnp.float32, buf="pws",
                                      slot=(so % 2) * wi + src)
            pod = pod + part
        if so > 0:
            # the inter-pod accumulator of this region arrives from the
            # left pod (its step so-1 covered the same region)
            prev = ctx.wait_read(ts.shape, jnp.float32, buf="ows",
                                 slot=so % 2, sig="orecv")
            pod = pod + prev
            if so < wo - 1:
                ctx.signal_op(left, sig="cap")
        acc = pod
        if so != wo - 1:
            ctx.signal_wait_until(sig="cap", value=1)
            ctx.putmem_signal_nbi(acc, right, buf="ows", slot=(so + 1) % 2,
                                  sig="orecv")
    ctx.barrier_all()
    return acc.astype(out_dtype)


def _push_rs_ring_ag_emulated(chain, operand, statics, *, axis, world,
                              out_dtype, cid):
    """Chained boundary protocol: Alg. 3 push (rs half) -> rank-local
    ``mid`` -> Fig. 4 ring (ag half), in ONE context with NO barrier
    between the halves. The ag ring's initial credit is granted before
    the rs half starts, so a fast rank's first ag hop lands while slow
    ranks are still pushing/reducing rs partials — the boundary
    collective's exposed latency hides behind the rs tail. Per-half
    workspaces/signals ("ws_rs"/"recv_rs" vs "ws_ag"/"recv_ag"/"cap_ag")
    keep the overlapping halves from aliasing; span labels ``rs_s{s}`` /
    ``mid`` / ``ag_s{s}`` keep the halves apart in traces."""
    assert isinstance(chain, ChainTile), chain
    n_rs, n_ag = chain.n_rs, chain.n_ag
    rs_statics = statics[:n_rs]
    ag_statics = statics[n_rs:n_rs + n_ag]
    mid_statics = statics[n_rs + n_ag:]
    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)
    m_blk = operand.shape[0] // world
    rs_ts = _tile_struct(chain.rs, _block(operand, 0, m_blk), rs_statics)

    ctx = em.ShmemCtx(axis, world, cid)
    ctx.barrier_all()
    # the ag ring's initial credit is granted BEFORE the rs half runs:
    # nothing separates the halves, so the first boundary hop can land
    # behind a neighbor still reducing (the fusion).
    ctx.signal_op(left, sig="cap_ag")

    # rs half — Alg. 3 push (peers' blocks first, own last), f32 partials
    for s in range(world):
        blk = lax.rem(me - s - 1 + 2 * world, world)
        partial = ctx.span("tile_compute",
                           lambda b: chain.rs(b, *rs_statics),
                           _block(operand, blk, m_blk),
                           name=f"rs_s{s}").astype(jnp.float32)
        ctx.putmem_signal_nbi(partial, blk, buf="ws_rs", slot=me,
                              sig="recv_rs")
    ctx.signal_wait_until(sig="recv_rs", value=world)
    acc = jnp.zeros(rs_ts.shape, jnp.float32)
    for r in range(world):
        acc = acc + ctx.read_symmetric(rs_ts.shape, jnp.float32,
                                       buf="ws_rs", slot=r)

    # boundary — rank-local mid on the owner's reduced block
    def _mid(a, *ms):
        reduced = a.astype(out_dtype)
        return chain.mid(reduced, *ms) if chain.mid is not None else reduced

    h = ctx.span("tile_compute", _mid, acc, *mid_statics, name="mid")

    # ag half — Fig. 4 ring + credit over the boundary activation
    ag_ts = _tile_struct(chain.ag, h, ag_statics)
    tile_m = ag_ts.shape[0]
    cur = h
    out = jnp.zeros((tile_m * world,) + ag_ts.shape[1:], out_dtype)
    for s in range(world):
        if s != world - 1:
            ctx.signal_wait_until(sig="cap_ag", value=1)
            ctx.putmem_signal_nbi(cur, right, buf="ws_ag", slot=(s + 1) % 2,
                                  sig="recv_ag")
        t = ctx.span("tile_compute", lambda c: chain.ag(c, *ag_statics), cur,
                     name=f"ag_s{s}").astype(out_dtype)
        owner = lax.rem(me - s + world, world)
        out = update_rows(out, t, owner * tile_m)
        if s != world - 1:
            cur = ctx.wait_read(h.shape, h.dtype, buf="ws_ag",
                                slot=(s + 1) % 2, sig="recv_ag")
            if s < world - 2:
                ctx.signal_op(left, sig="cap_ag")
    ctx.barrier_all()
    return out


# ---------------------------------------------------------------------------
# pltpu backend: one generic Pallas kernel per protocol
# ---------------------------------------------------------------------------


def _compiler_params(cid: int, scratch) -> pltpu.CompilerParams:
    """CompilerParams for a protocol kernel, with a scoped-VMEM limit
    sized from its VMEM footprint: the scratch buffers, as much again
    for the tile compute's values (its f32 product lives beside the
    buffers before the cast into the output tile), and 4 MiB for the
    compiler's own use, capped at the chip's VMEM. Whole-shard weights
    at real widths do not fit the default scoped limit."""
    vmem = sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in scratch if hasattr(s, "dtype"))
    limit = min(2 * vmem + (4 << 20), hw.local_spec().vmem_bytes)
    return pltpu.CompilerParams(collective_id=cid, vmem_limit_bytes=limit)


def _stage(refs, vmems, sem):
    copies = [pltpu.make_async_copy(r, v, sem) for r, v in zip(refs, vmems)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _ring_ag_body(*refs, tile, axis, world, n_static, tile_m, out_dtype):
    (chunk_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, ws_ref = rest[n_static], rest[n_static + 1]
    chunk_vmem = rest[n_static + 2]
    static_vmems = rest[n_static + 3:2 * n_static + 3]
    o_vmem = rest[2 * n_static + 3]
    local_sem, send_sem, recv_sem, cap_sem = rest[2 * n_static + 4:]

    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)

    # Symmetric-memory handshake: every rank's workspace must exist before
    # any one-sided put lands in it (paper: barrier_all after allocation).
    tpu_backend.barrier_all(axis, world)

    # Stage the statics into VMEM once; copy my chunk into ring slot 0.
    _stage((chunk_ref,) + tuple(static_refs),
           (ws_ref.at[0],) + tuple(static_vmems), local_sem)

    # Initially my right neighbor's slot 1 is free: grant 1 credit.
    tpu_backend.signal_op(cap_sem, left, axis=axis)

    for s in range(world):
        slot = s % 2
        send = None
        if s != world - 1:
            # producer: wait for a free slot at the right neighbor, then
            # putmem_signal my current chunk into their next slot.
            tpu_backend.signal_wait_until(cap_sem, 1)
            send = tpu_backend.putmem_signal_nbi(
                ws_ref.at[slot], ws_ref.at[(s + 1) % 2],
                send_sem, recv_sem, right, axis=axis)

        # consumer: chunk of step s is rank (me - s)'s data; its arrival
        # is ordered by recv_sem via the previous step's wait.
        _stage((ws_ref.at[slot],), (chunk_vmem,), local_sem)

        # the tile compute overlaps the in-flight remote DMA of chunk s+1
        with tpu_backend.annotate("tile_compute", f"s{s}"):
            o_vmem[...] = tile(
                chunk_vmem[...], *[v[...] for v in static_vmems]
            ).astype(out_dtype)
        owner = lax.rem(me - s + world, world)
        _stage((o_vmem,), (o_ref.at[pl.ds(owner * tile_m, tile_m)],), local_sem)

        if send is not None:
            # wait: my send drained + my incoming chunk has landed.
            send.wait()
        # Slot fully consumed — BOTH readers done (VMEM stage AND the
        # outgoing remote DMA). Only now may the left neighbor overwrite
        # it. Skip grants beyond the W-1 sends the neighbor makes.
        if s < world - 2:
            tpu_backend.signal_op(cap_sem, left, axis=axis)


def _ring_ag_pltpu(tile, chunk, statics, *, axis, world, out_dtype, cid):
    ts = _tile_struct(tile, chunk, statics)
    body = functools.partial(
        _ring_ag_body, tile=tile, axis=axis, world=world,
        n_static=len(statics), tile_m=ts.shape[0], out_dtype=out_dtype)
    scratch = ([pltpu.VMEM(chunk.shape, chunk.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(ts.shape, out_dtype),
                  pltpu.SemaphoreType.DMA,
                  pltpu.SemaphoreType.DMA,
                  pltpu.SemaphoreType.DMA,
                  pltpu.SemaphoreType.REGULAR])
    out, _ws = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct((ts.shape[0] * world,) + ts.shape[1:], out_dtype),
            jax.ShapeDtypeStruct((2,) + chunk.shape, chunk.dtype),  # ring ws
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(chunk, *statics)
    return out


def _one_shot_ag_body(*refs, tile, axis, world, n_static, tile_m, out_dtype):
    (chunk_ref, *rest) = refs
    static_refs = rest[:n_static]
    gather_direct = tile is _identity and n_static == 0
    if gather_direct:
        o_ref = rest[n_static]
        local_sem, send_sem, recv_sem = rest[n_static + 1:]
    else:
        o_ref, ws_ref = rest[n_static], rest[n_static + 1]
        chunk_vmem = rest[n_static + 2]
        static_vmems = rest[n_static + 3:2 * n_static + 3]
        o_vmem = rest[2 * n_static + 3]
        local_sem, send_sem, recv_sem = rest[2 * n_static + 4:]

    me = lax.axis_index(axis)
    tpu_backend.barrier_all(axis, world)

    # landing site: the gathered output itself (plain AllGather) or the
    # symmetric workspace slot `me` (a tile compute consumes the chunks)
    dst = (o_ref.at[pl.ds(me * tile_m, tile_m)] if gather_direct
           else ws_ref.at[me])
    lc = pltpu.make_async_copy(chunk_ref, dst, local_sem)
    lc.start()

    # One-shot: all W-1 puts issued before any wait (Alg. 4 structure —
    # no skew accumulation from a serial loop).
    sends = []
    for off in range(1, world):
        peer = lax.rem(me + off, world)
        sends.append(tpu_backend.putmem_signal_nbi(
            chunk_ref, dst, send_sem, recv_sem, peer, axis=axis))
    lc.wait()
    # SPMD symmetry: my W-1 incoming messages are my peers' sends with the
    # same shape/semaphore, so waiting my own descriptors consumes exactly
    # the right signal count (send-drain + W-1 arrivals).
    tpu_backend.quiet(*sends)

    if not gather_direct:
        if n_static:
            _stage(tuple(static_refs), tuple(static_vmems), local_sem)
        for r in range(world):
            _stage((ws_ref.at[r],), (chunk_vmem,), local_sem)
            with tpu_backend.annotate("tile_compute", f"r{r}"):
                o_vmem[...] = tile(
                    chunk_vmem[...], *[v[...] for v in static_vmems]
                ).astype(out_dtype)
            _stage((o_vmem,), (o_ref.at[pl.ds(r * tile_m, tile_m)],), local_sem)


def _one_shot_ag_pltpu(tile, chunk, statics, *, axis, world, out_dtype, cid):
    ts = _tile_struct(tile, chunk, statics)
    gather_direct = tile is _identity and not statics
    body = functools.partial(
        _one_shot_ag_body, tile=tile, axis=axis, world=world,
        n_static=len(statics), tile_m=ts.shape[0], out_dtype=out_dtype)
    out_shape = [jax.ShapeDtypeStruct(
        (ts.shape[0] * world,) + ts.shape[1:], out_dtype)]
    scratch = [pltpu.SemaphoreType.DMA] * 3
    if not gather_direct:
        out_shape.append(  # symmetric landing workspace
            jax.ShapeDtypeStruct((world,) + chunk.shape, chunk.dtype))
        scratch = ([pltpu.VMEM(chunk.shape, chunk.dtype)]
                   + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
                   + [pltpu.VMEM(ts.shape, out_dtype)] + scratch)
    outs = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(out_shape),
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(chunk, *statics)
    return outs[0] if isinstance(outs, (tuple, list)) else outs


def _push_rs_body(*refs, tile, axis, world, n_static, m_blk, one_shot,
                  out_dtype, decode=None):
    (a_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, ws_ref = rest[n_static], rest[n_static + 1]
    stage_ref = rest[n_static + 2] if one_shot else None
    base = n_static + (3 if one_shot else 2)
    a_vmem = rest[base]
    static_vmems = rest[base + 1:base + 1 + n_static]
    p_vmem = rest[base + 1 + n_static]
    # under a wire dtype p_vmem holds the packed partial; the decoded f32
    # accumulator needs its own (differently-shaped) output buffer
    o_vmem = rest[base + 2 + n_static] if decode is not None else None
    sem_base = base + 2 + n_static + (1 if decode is not None else 0)
    local_sem, send_sem, recv_sem = rest[sem_base:]

    me = lax.axis_index(axis)
    tpu_backend.barrier_all(axis, world)
    if n_static:
        _stage(tuple(static_refs), tuple(static_vmems), local_sem)

    def compute(blk):
        _stage((a_ref.at[pl.ds(blk * m_blk, m_blk)],), (a_vmem,), local_sem)
        with tpu_backend.annotate("tile_compute"):
            partial = tile(a_vmem[...], *[v[...] for v in static_vmems])
        # packed wire buffers are pushed verbatim (a cast would corrupt
        # the bytes); plain partials land in out_dtype as before
        p_vmem[...] = partial if decode is not None else partial.astype(out_dtype)

    sends = []
    if one_shot:
        # low-latency variant: ALL partials computed into local staging
        # first, then the W-1 puts issued up-front with no waits between
        # (own block, off 0, is a local copy — no self-targeted DMA).
        for off in range(world):
            compute(lax.rem(me + off, world))
            _stage((p_vmem,), (stage_ref.at[off],), local_sem)
        _stage((stage_ref.at[0],), (ws_ref.at[me],), local_sem)
        for off in range(1, world):
            tgt = lax.rem(me + off, world)
            sends.append(tpu_backend.putmem_signal_nbi(
                stage_ref.at[off], ws_ref.at[me], send_sem, recv_sem, tgt,
                axis=axis))
        for send in sends:
            send.wait_send()
    else:
        for s in range(world):
            # Alg. 3 swizzle: peers' blocks first, own block last
            blk = lax.rem(me - s - 1 + 2 * world, world)
            compute(blk)
            if s == world - 1:
                # my own block: local copy into my slot of my workspace
                _stage((p_vmem,), (ws_ref.at[me],), local_sem)
            else:
                # one-sided push + arrival signal to the owner (slot = me)
                send = tpu_backend.putmem_signal_nbi(
                    p_vmem, ws_ref.at[me], send_sem, recv_sem, blk, axis=axis)
                # the next step's compute overlaps this DMA; drain before
                # reusing p_vmem (single partial buffer)
                send.wait_send()
                sends.append(send)

    # signal_wait for the W-1 remote partials (SPMD symmetry: waiting my
    # own descriptors consumes my peers' arrivals), then the f32 reduction
    for send in sends:
        send.wait_recv()
    acc_vmem = p_vmem if decode is None else o_vmem
    acc = jnp.zeros(acc_vmem.shape, jnp.float32)
    for r in range(world):
        _stage((ws_ref.at[r],), (p_vmem,), local_sem)
        if decode is None:
            acc = acc + p_vmem[...].astype(jnp.float32)
        else:
            with tpu_backend.annotate("decode", f"r{r}"):
                acc = acc + decode(p_vmem[...])
    acc_vmem[...] = acc.astype(out_dtype)
    _stage((acc_vmem,), (o_ref,), local_sem)


def _rs_pltpu(tile, operand, statics, *, axis, world, out_dtype, cid,
              one_shot, decode=None):
    m_blk = operand.shape[0] // world
    blk_struct = jax.ShapeDtypeStruct((m_blk,) + operand.shape[1:],
                                      operand.dtype)
    ts = _tile_struct(tile, blk_struct, statics)
    # under a wire dtype the riding partial is the packed buffer (ts) and
    # the output block is its decoded shape
    ws_dtype = out_dtype if decode is None else ts.dtype
    out_struct = ts if decode is None else jax.eval_shape(decode, ts)
    body = functools.partial(
        _push_rs_body, tile=tile, axis=axis, world=world,
        n_static=len(statics), m_blk=m_blk, one_shot=one_shot,
        out_dtype=out_dtype, decode=decode)
    out_shape = [
        jax.ShapeDtypeStruct(out_struct.shape, out_dtype),
        jax.ShapeDtypeStruct((world,) + ts.shape, ws_dtype),  # landing ws
    ]
    if one_shot:
        out_shape.append(  # local staging for the up-front puts
            jax.ShapeDtypeStruct((world,) + ts.shape, ws_dtype))
    scratch = ([pltpu.VMEM(blk_struct.shape, operand.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(ts.shape, ws_dtype)])
    if decode is not None:
        scratch.append(pltpu.VMEM(out_struct.shape, out_dtype))
    scratch += [pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA,
                pltpu.SemaphoreType.DMA]
    outs = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(out_shape),
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(operand, *statics)
    return outs[0]


def _bidir_ring_ag_body(*refs, tile, axis, world, n_static, half_rows, tile_h,
                        out_dtype):
    (chunk_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, wsf_ref, wsb_ref = rest[n_static:n_static + 3]
    half_vmem = rest[n_static + 3]
    static_vmems = rest[n_static + 4:2 * n_static + 4]
    o_vmem = rest[2 * n_static + 4]
    (local_sem, send_f, recv_f, send_b, recv_b,
     cap_f, cap_b) = rest[2 * n_static + 5:]

    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)
    tile_m = 2 * tile_h

    tpu_backend.barrier_all(axis, world)

    # Stage statics once; copy my chunk halves into each ring's slot 0.
    _stage((chunk_ref.at[pl.ds(0, half_rows)],
            chunk_ref.at[pl.ds(half_rows, half_rows)]) + tuple(static_refs),
           (wsf_ref.at[0], wsb_ref.at[0]) + tuple(static_vmems), local_sem)

    # One initial credit per direction (fwd: I receive from left; bwd:
    # from right) — the neighbor's slot 1 starts free.
    tpu_backend.signal_op(cap_f, left, axis=axis)
    tpu_backend.signal_op(cap_b, right, axis=axis)

    for s in range(world):
        slot = s % 2
        sends = ()
        if s != world - 1:
            tpu_backend.signal_wait_until(cap_f, 1)
            sf = tpu_backend.putmem_signal_nbi(
                wsf_ref.at[slot], wsf_ref.at[(s + 1) % 2],
                send_f, recv_f, right, axis=axis)
            tpu_backend.signal_wait_until(cap_b, 1)
            sb = tpu_backend.putmem_signal_nbi(
                wsb_ref.at[slot], wsb_ref.at[(s + 1) % 2],
                send_b, recv_b, left, axis=axis)
            sends = (sf, sb)

        # both directions' tiles overlap the two in-flight remote DMAs;
        # arrivals of slot s were ordered by the previous step's waits.
        for direction, ws_ref, owner in (
                (0, wsf_ref, lax.rem(me - s + world, world)),
                (1, wsb_ref, lax.rem(me + s, world))):
            _stage((ws_ref.at[slot],), (half_vmem,), local_sem)
            with tpu_backend.annotate("tile_compute", f"s{s}d{direction}"):
                o_vmem[...] = tile(
                    half_vmem[...], *[v[...] for v in static_vmems]
                ).astype(out_dtype)
            _stage((o_vmem,),
                   (o_ref.at[pl.ds(owner * tile_m + direction * tile_h,
                                   tile_h)],),
                   local_sem)

        for send in sends:
            # send drained + my incoming half landed (SPMD symmetry)
            send.wait()
        if s < world - 2:
            # both slots fully consumed — the neighbors may overwrite
            tpu_backend.signal_op(cap_f, left, axis=axis)
            tpu_backend.signal_op(cap_b, right, axis=axis)


def _bidir_ring_ag_pltpu(tile, chunk, statics, *, axis, world, out_dtype, cid):
    m = chunk.shape[0]
    if world < 3 or m % 2:
        # mirror the graph lowering: bidir degenerates to ring
        return _ring_ag_pltpu(tile, chunk, statics, axis=axis, world=world,
                              out_dtype=out_dtype, cid=cid)
    half_rows = m // 2
    half_struct = jax.ShapeDtypeStruct((half_rows,) + chunk.shape[1:],
                                       chunk.dtype)
    ts = _tile_struct(tile, half_struct, statics)
    tile_h = ts.shape[0]
    body = functools.partial(
        _bidir_ring_ag_body, tile=tile, axis=axis, world=world,
        n_static=len(statics), half_rows=half_rows, tile_h=tile_h,
        out_dtype=out_dtype)
    scratch = ([pltpu.VMEM(half_struct.shape, chunk.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(ts.shape, out_dtype),
                  pltpu.SemaphoreType.DMA,   # local staging
                  pltpu.SemaphoreType.DMA,   # fwd send
                  pltpu.SemaphoreType.DMA,   # fwd recv
                  pltpu.SemaphoreType.DMA,   # bwd send
                  pltpu.SemaphoreType.DMA,   # bwd recv
                  pltpu.SemaphoreType.REGULAR,   # fwd credits
                  pltpu.SemaphoreType.REGULAR])  # bwd credits
    out, _wsf, _wsb = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((2 * tile_h * world,) + ts.shape[1:],
                                 out_dtype),
            jax.ShapeDtypeStruct((2,) + half_struct.shape, chunk.dtype),
            jax.ShapeDtypeStruct((2,) + half_struct.shape, chunk.dtype),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(chunk, *statics)
    return out


def _one_shot_a2a_body(*refs, tile, axis, world, n_static, out_dtype,
                       a2a_direct):
    (xs_ref, *rest) = refs
    static_refs = rest[:n_static]
    if a2a_direct:
        o_ref = rest[n_static]
        local_sem, send_sem, recv_sem = rest[n_static + 1:]
    else:
        o_ref, ws_ref = rest[n_static], rest[n_static + 1]
        blk_vmem = rest[n_static + 2]
        static_vmems = rest[n_static + 3:2 * n_static + 3]
        o_vmem = rest[2 * n_static + 3]
        local_sem, send_sem, recv_sem = rest[2 * n_static + 4:]

    me = lax.axis_index(axis)
    tpu_backend.barrier_all(axis, world)

    # landing site: the output itself (pure a2a data movement) or the
    # symmetric workspace (a tile compute consumes the blocks). Slot =
    # sender id: my block for PE t lands in t's row ``me``.
    dst = o_ref if a2a_direct else ws_ref
    lc = pltpu.make_async_copy(xs_ref.at[me], dst.at[me], local_sem)
    lc.start()

    # One-shot: all W-1 puts issued before any wait — no serial chain.
    sends = []
    for off in range(1, world):
        tgt = lax.rem(me + off, world)
        sends.append(tpu_backend.putmem_signal_nbi(
            xs_ref.at[tgt], dst.at[me], send_sem, recv_sem, tgt, axis=axis))
    lc.wait()
    # SPMD symmetry: waiting my own descriptors consumes exactly my send
    # drains + my W-1 arrivals.
    tpu_backend.quiet(*sends)

    if not a2a_direct:
        if n_static:
            _stage(tuple(static_refs), tuple(static_vmems), local_sem)
        for src in range(world):
            _stage((ws_ref.at[src],), (blk_vmem,), local_sem)
            with tpu_backend.annotate("tile_compute", f"src{src}"):
                o_vmem[...] = tile(
                    blk_vmem[...], *[v[...] for v in static_vmems]
                ).astype(out_dtype)
            _stage((o_vmem,), (o_ref.at[src],), local_sem)


def _one_shot_a2a_pltpu(tile, xs, statics, *, axis, world, out_dtype, cid):
    assert xs.shape[0] == world, (xs.shape, world)
    blk_struct = jax.ShapeDtypeStruct(xs.shape[1:], xs.dtype)
    ts = _tile_struct(tile, blk_struct, statics)
    a2a_direct = (tile is _identity and not statics
                  and jnp.dtype(out_dtype) == xs.dtype)
    body = functools.partial(
        _one_shot_a2a_body, tile=tile, axis=axis, world=world,
        n_static=len(statics), out_dtype=out_dtype, a2a_direct=a2a_direct)
    out_shape = [jax.ShapeDtypeStruct((world,) + ts.shape, out_dtype)]
    scratch = [pltpu.SemaphoreType.DMA] * 3
    if not a2a_direct:
        out_shape.append(  # symmetric landing workspace
            jax.ShapeDtypeStruct((world,) + xs.shape[1:], xs.dtype))
        scratch = ([pltpu.VMEM(xs.shape[1:], xs.dtype)]
                   + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
                   + [pltpu.VMEM(ts.shape, out_dtype)] + scratch)
    outs = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(out_shape),
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(xs, *statics)
    return outs[0] if isinstance(outs, (tuple, list)) else outs


def _ring_fold_body(*refs, fold, axis, world, n_static, n_state,
                    state_treedef, out_dtype):
    (chunk_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, ws_ref = rest[n_static], rest[n_static + 1]
    chunk_vmem = rest[n_static + 2]
    static_vmems = rest[n_static + 3:2 * n_static + 3]
    state_vmems = rest[2 * n_static + 3:2 * n_static + 3 + n_state]
    o_vmem = rest[2 * n_static + 3 + n_state]
    local_sem, send_sem, recv_sem, cap_sem = rest[2 * n_static + 4 + n_state:]

    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)

    tpu_backend.barrier_all(axis, world)
    _stage((chunk_ref,) + tuple(static_refs),
           (ws_ref.at[0],) + tuple(static_vmems), local_sem)
    # Initially my right neighbor's slot 1 is free: grant 1 credit.
    tpu_backend.signal_op(cap_sem, left, axis=axis)

    def statics():
        return [v[...] for v in static_vmems]

    def write_state(state):
        for sv, leaf in zip(state_vmems, jax.tree_util.tree_leaves(state)):
            sv[...] = leaf

    def read_state():
        return jax.tree_util.tree_unflatten(
            state_treedef, [sv[...] for sv in state_vmems])

    # resident f32 fold state, carried across steps in VMEM scratch
    # (chunk_vmem holds my own chunk after this — step 0 reuses it)
    _stage((ws_ref.at[0],), (chunk_vmem,), local_sem)
    write_state(fold.init(chunk_vmem[...], *statics()))

    for s in range(world):
        slot = s % 2
        send = None
        if s != world - 1:
            tpu_backend.signal_wait_until(cap_sem, 1)
            send = tpu_backend.putmem_signal_nbi(
                ws_ref.at[slot], ws_ref.at[(s + 1) % 2],
                send_sem, recv_sem, right, axis=axis)
        # the fold of chunk s overlaps the in-flight remote DMA of s+1;
        # s=0's chunk is already VMEM-resident from the init staging
        if s != 0:
            _stage((ws_ref.at[slot],), (chunk_vmem,), local_sem)
        owner = lax.rem(me - s + world, world)
        with tpu_backend.annotate("tile_compute", f"s{s}"):
            write_state(fold.fold(read_state(), chunk_vmem[...], owner,
                                  *statics()))
        if send is not None:
            send.wait()
        if s < world - 2:
            tpu_backend.signal_op(cap_sem, left, axis=axis)

    with tpu_backend.annotate("tile_compute", "finalize"):
        o_vmem[...] = fold.finalize(read_state(), *statics()).astype(out_dtype)
    _stage((o_vmem,), (o_ref,), local_sem)


def _ring_fold_pltpu(fold, chunk, statics, *, axis, world, out_dtype, cid):
    assert isinstance(fold, FoldTile), fold
    chunk_struct = jax.ShapeDtypeStruct(chunk.shape, chunk.dtype)
    state_struct = jax.eval_shape(fold.init, chunk_struct, *statics)
    state_leaves, state_treedef = jax.tree_util.tree_flatten(state_struct)
    out_struct = jax.eval_shape(fold.finalize, state_struct, *statics)
    body = functools.partial(
        _ring_fold_body, fold=fold, axis=axis, world=world,
        n_static=len(statics), n_state=len(state_leaves),
        state_treedef=state_treedef, out_dtype=out_dtype)
    scratch = ([pltpu.VMEM(chunk.shape, chunk.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(leaf.shape, leaf.dtype) for leaf in state_leaves]
               + [pltpu.VMEM(out_struct.shape, out_dtype),
                  pltpu.SemaphoreType.DMA,
                  pltpu.SemaphoreType.DMA,
                  pltpu.SemaphoreType.DMA,
                  pltpu.SemaphoreType.REGULAR])
    out, _ws = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_shape=[
            jax.ShapeDtypeStruct(out_struct.shape, out_dtype),
            jax.ShapeDtypeStruct((2,) + chunk.shape, chunk.dtype),  # ring ws
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(chunk, *statics)
    return out


def _two_level_ag_body(*refs, tile, axes, worlds, n_static, tile_m, out_dtype):
    # axes/worlds ordered (outer, inner), matching the 2D device ids
    outer, inner = axes
    wo, wi = worlds
    (chunk_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, pws_ref, ows_ref = rest[n_static:n_static + 3]
    chunk_vmem = rest[n_static + 3]
    static_vmems = rest[n_static + 4:2 * n_static + 4]
    o_vmem = rest[2 * n_static + 4]
    (local_sem, psend, precv, osend, orecv, cap_sem) = rest[2 * n_static + 5:]

    iid = lax.axis_index(inner)
    oid = lax.axis_index(outer)
    left = lax.rem(oid + wo - 1, wo)
    right = lax.rem(oid + 1, wo)

    tpu_backend.barrier_all_grid(axes, worlds)
    _stage((chunk_ref,) + tuple(static_refs),
           (ows_ref.at[0],) + tuple(static_vmems), local_sem)
    if wo > 1:
        tpu_backend.signal_op(cap_sem, (left, iid))

    for so in range(wo):
        slot = so % 2
        region = lax.rem(oid - so + wo, wo)
        send_o = None
        if so != wo - 1:
            # the slow-link hop of region so+1 overlaps this region's
            # pod-local exchange + compute (ring_ag credits over pods)
            tpu_backend.signal_wait_until(cap_sem, 1)
            send_o = tpu_backend.putmem_signal_nbi(
                ows_ref.at[slot], ows_ref.at[(so + 1) % 2],
                osend, orecv, (right, iid))
        # pod-local one_shot: local copy for self + Wi-1 puts, all issued
        # before any wait (the emulated body's per-source signals become
        # the SPMD-symmetric descriptor waits here)
        lc = pltpu.make_async_copy(
            ows_ref.at[slot], pws_ref.at[slot * wi + iid], local_sem)
        lc.start()
        sends = []
        for off in range(1, wi):
            sends.append(tpu_backend.putmem_signal_nbi(
                ows_ref.at[slot], pws_ref.at[slot * wi + iid],
                psend, precv, (oid, lax.rem(iid + off, wi))))
        lc.wait()
        tpu_backend.quiet(*sends)
        for d in range(wi):
            src = lax.rem(iid - d + wi, wi)
            _stage((pws_ref.at[slot * wi + src],), (chunk_vmem,), local_sem)
            with tpu_backend.annotate("tile_compute", f"o{so}d{d}"):
                o_vmem[...] = tile(
                    chunk_vmem[...], *[v[...] for v in static_vmems]
                ).astype(out_dtype)
            owner = region * wi + src
            _stage((o_vmem,), (o_ref.at[pl.ds(owner * tile_m, tile_m)],),
                   local_sem)
        if send_o is not None:
            send_o.wait()
        if so < wo - 2:
            tpu_backend.signal_op(cap_sem, (left, iid))


def _two_level_ag_pltpu(tile, chunk, statics, *, axis, world, out_dtype, cid):
    inner, outer = axis
    wi, wo = world
    ts = _tile_struct(tile, chunk, statics)
    body = functools.partial(
        _two_level_ag_body, tile=tile, axes=(outer, inner), worlds=(wo, wi),
        n_static=len(statics), tile_m=ts.shape[0], out_dtype=out_dtype)
    scratch = ([pltpu.VMEM(chunk.shape, chunk.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(ts.shape, out_dtype),
                  pltpu.SemaphoreType.DMA,   # local staging
                  pltpu.SemaphoreType.DMA,   # pod send
                  pltpu.SemaphoreType.DMA,   # pod recv
                  pltpu.SemaphoreType.DMA,   # outer send
                  pltpu.SemaphoreType.DMA,   # outer recv
                  pltpu.SemaphoreType.REGULAR])  # outer credits
    out, _pws, _ows = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((ts.shape[0] * wi * wo,) + ts.shape[1:],
                                 out_dtype),
            jax.ShapeDtypeStruct((2 * wi,) + chunk.shape, chunk.dtype),  # pod
            jax.ShapeDtypeStruct((2,) + chunk.shape, chunk.dtype),  # outer
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(chunk, *statics)
    return out


def _two_level_rs_body(*refs, tile, axes, worlds, n_static, m_blk, out_dtype):
    outer, inner = axes
    wo, wi = worlds
    (a_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, pws_ref, ows_ref, stage_ref = rest[n_static:n_static + 4]
    a_vmem = rest[n_static + 4]
    static_vmems = rest[n_static + 5:2 * n_static + 5]
    p_vmem = rest[2 * n_static + 5]       # f32 partial / pod landing
    acc_vmem = rest[2 * n_static + 6]     # f32 inter-pod accumulator
    o_vmem = rest[2 * n_static + 7]
    (local_sem, psend, precv, osend, orecv, cap_sem) = rest[2 * n_static + 8:]

    iid = lax.axis_index(inner)
    oid = lax.axis_index(outer)
    left = lax.rem(oid + wo - 1, wo)
    right = lax.rem(oid + 1, wo)

    tpu_backend.barrier_all_grid(axes, worlds)
    if n_static:
        _stage(tuple(static_refs), tuple(static_vmems), local_sem)
    if wo > 1:
        tpu_backend.signal_op(cap_sem, (left, iid))

    for so in range(wo):
        slot = so % 2
        region = lax.rem(oid - so - 1 + 2 * wo, wo)
        # pod-local one_shot RS: all Wi partials into local staging first
        for off in range(wi):
            blk = region * wi + lax.rem(iid + off, wi)
            _stage((a_ref.at[pl.ds(blk * m_blk, m_blk)],), (a_vmem,),
                   local_sem)
            with tpu_backend.annotate("tile_compute", f"o{so}off{off}"):
                p_vmem[...] = tile(
                    a_vmem[...], *[v[...] for v in static_vmems]
                ).astype(jnp.float32)
            _stage((p_vmem,), (stage_ref.at[off],), local_sem)
        lc = pltpu.make_async_copy(
            stage_ref.at[0], pws_ref.at[slot * wi + iid], local_sem)
        lc.start()
        sends = []
        for off in range(1, wi):
            sends.append(tpu_backend.putmem_signal_nbi(
                stage_ref.at[off], pws_ref.at[slot * wi + iid],
                psend, precv, (oid, lax.rem(iid + off, wi))))
        lc.wait()
        tpu_backend.quiet(*sends)
        acc = jnp.zeros(p_vmem.shape, jnp.float32)
        for d in range(wi):
            src = lax.rem(iid - d + wi, wi)
            _stage((pws_ref.at[slot * wi + src],), (p_vmem,), local_sem)
            acc = acc + p_vmem[...]
        if so > 0:
            # this region's inter-pod accumulator arrived from the left
            # pod; its landing was ordered by the previous step's send
            # wait (SPMD symmetry)
            _stage((ows_ref.at[slot],), (acc_vmem,), local_sem)
            acc = acc + acc_vmem[...]
            if so < wo - 1:
                tpu_backend.signal_op(cap_sem, (left, iid))
        acc_vmem[...] = acc
        if so != wo - 1:
            tpu_backend.signal_wait_until(cap_sem, 1)
            send = tpu_backend.putmem_signal_nbi(
                acc_vmem, ows_ref.at[(so + 1) % 2], osend, orecv,
                (right, iid))
            send.wait()

    o_vmem[...] = acc_vmem[...].astype(out_dtype)
    _stage((o_vmem,), (o_ref,), local_sem)


def _two_level_rs_pltpu(tile, operand, statics, *, axis, world, out_dtype,
                        cid):
    inner, outer = axis
    wi, wo = world
    m_blk = operand.shape[0] // (wi * wo)
    blk_struct = jax.ShapeDtypeStruct((m_blk,) + operand.shape[1:],
                                      operand.dtype)
    ts = _tile_struct(tile, blk_struct, statics)
    body = functools.partial(
        _two_level_rs_body, tile=tile, axes=(outer, inner), worlds=(wo, wi),
        n_static=len(statics), m_blk=m_blk, out_dtype=out_dtype)
    scratch = ([pltpu.VMEM(blk_struct.shape, operand.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(ts.shape, jnp.float32),
                  pltpu.VMEM(ts.shape, jnp.float32),
                  pltpu.VMEM(ts.shape, out_dtype),
                  pltpu.SemaphoreType.DMA,   # local staging
                  pltpu.SemaphoreType.DMA,   # pod send
                  pltpu.SemaphoreType.DMA,   # pod recv
                  pltpu.SemaphoreType.DMA,   # outer send
                  pltpu.SemaphoreType.DMA,   # outer recv
                  pltpu.SemaphoreType.REGULAR])  # outer credits
    outs = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_shape=[
            jax.ShapeDtypeStruct(ts.shape, out_dtype),
            jax.ShapeDtypeStruct((2 * wi,) + ts.shape, jnp.float32),  # pod
            jax.ShapeDtypeStruct((2,) + ts.shape, jnp.float32),  # outer
            jax.ShapeDtypeStruct((wi,) + ts.shape, jnp.float32),  # staging
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(operand, *statics)
    return outs[0]


def _push_rs_ring_ag_body(*refs, chain, axis, world, n_rs, n_ag, n_mid,
                          m_blk, tile_m, out_dtype, h_dtype):
    n_static = n_rs + n_ag + n_mid
    (a_ref, *rest) = refs
    static_refs = rest[:n_static]
    o_ref, wsr_ref, wsa_ref = rest[n_static:n_static + 3]
    a_vmem = rest[n_static + 3]
    static_vmems = rest[n_static + 4:2 * n_static + 4]
    p_vmem = rest[2 * n_static + 4]       # f32 rs partial / landed partial
    h_vmem = rest[2 * n_static + 5]       # boundary activation chunk
    o_vmem = rest[2 * n_static + 6]
    (local_sem, rs_send, rs_recv, ag_send, ag_recv,
     ag_cap) = rest[2 * n_static + 7:]

    me = lax.axis_index(axis)
    left = lax.rem(me + world - 1, world)
    right = lax.rem(me + 1, world)

    tpu_backend.barrier_all(axis, world)
    if n_static:
        _stage(tuple(static_refs), tuple(static_vmems), local_sem)
    # the ag ring's initial credit, granted before the rs half even
    # starts — no barrier separates the halves (the fusion)
    tpu_backend.signal_op(ag_cap, left, axis=axis)

    # --- rs half: Alg. 3 push into the rs workspace (f32 partials)
    sends = []
    for s in range(world):
        blk = lax.rem(me - s - 1 + 2 * world, world)
        _stage((a_ref.at[pl.ds(blk * m_blk, m_blk)],), (a_vmem,), local_sem)
        with tpu_backend.annotate("tile_compute", f"rs_s{s}"):
            p_vmem[...] = chain.rs(
                a_vmem[...], *[v[...] for v in static_vmems[:n_rs]]
            ).astype(jnp.float32)
        if s == world - 1:
            _stage((p_vmem,), (wsr_ref.at[me],), local_sem)
        else:
            send = tpu_backend.putmem_signal_nbi(
                p_vmem, wsr_ref.at[me], rs_send, rs_recv, blk, axis=axis)
            # next step's compute overlaps the DMA; drain before reusing
            # p_vmem (single partial buffer)
            send.wait_send()
            sends.append(send)
    for send in sends:
        send.wait_recv()
    acc = jnp.zeros(p_vmem.shape, jnp.float32)
    for r in range(world):
        _stage((wsr_ref.at[r],), (p_vmem,), local_sem)
        acc = acc + p_vmem[...]

    # --- boundary: rank-local mid, landed into the ag ring's slot 0
    with tpu_backend.annotate("tile_compute", "mid"):
        reduced = acc.astype(out_dtype)
        if chain.mid is not None:
            reduced = chain.mid(
                reduced, *[v[...] for v in static_vmems[n_rs + n_ag:]])
        h_vmem[...] = reduced.astype(h_dtype)
    _stage((h_vmem,), (wsa_ref.at[0],), local_sem)

    # --- ag half: Fig. 4 ring + credit over the boundary activation
    for s in range(world):
        slot = s % 2
        send = None
        if s != world - 1:
            tpu_backend.signal_wait_until(ag_cap, 1)
            send = tpu_backend.putmem_signal_nbi(
                wsa_ref.at[slot], wsa_ref.at[(s + 1) % 2],
                ag_send, ag_recv, right, axis=axis)
        _stage((wsa_ref.at[slot],), (h_vmem,), local_sem)
        with tpu_backend.annotate("tile_compute", f"ag_s{s}"):
            o_vmem[...] = chain.ag(
                h_vmem[...], *[v[...] for v in static_vmems[n_rs:n_rs + n_ag]]
            ).astype(out_dtype)
        owner = lax.rem(me - s + world, world)
        _stage((o_vmem,), (o_ref.at[pl.ds(owner * tile_m, tile_m)],),
               local_sem)
        if send is not None:
            send.wait()
        if s < world - 2:
            tpu_backend.signal_op(ag_cap, left, axis=axis)


def _push_rs_ring_ag_pltpu(chain, operand, statics, *, axis, world, out_dtype,
                           cid):
    assert isinstance(chain, ChainTile), chain
    n_rs, n_ag = chain.n_rs, chain.n_ag
    rs_statics = statics[:n_rs]
    ag_statics = statics[n_rs:n_rs + n_ag]
    mid_statics = statics[n_rs + n_ag:]
    m_blk = operand.shape[0] // world
    blk_struct = jax.ShapeDtypeStruct((m_blk,) + operand.shape[1:],
                                      operand.dtype)
    rs_ts = _tile_struct(chain.rs, blk_struct, rs_statics)

    def _boundary(acc, *ms):
        reduced = acc.astype(out_dtype)
        return chain.mid(reduced, *ms) if chain.mid is not None else reduced

    h_struct = jax.eval_shape(
        _boundary, jax.ShapeDtypeStruct(rs_ts.shape, jnp.float32),
        *mid_statics)
    ag_ts = _tile_struct(chain.ag, h_struct, ag_statics)
    body = functools.partial(
        _push_rs_ring_ag_body, chain=chain, axis=axis, world=world,
        n_rs=n_rs, n_ag=n_ag, n_mid=len(mid_statics), m_blk=m_blk,
        tile_m=ag_ts.shape[0], out_dtype=out_dtype, h_dtype=h_struct.dtype)
    scratch = ([pltpu.VMEM(blk_struct.shape, operand.dtype)]
               + [pltpu.VMEM(s.shape, s.dtype) for s in statics]
               + [pltpu.VMEM(rs_ts.shape, jnp.float32),
                  pltpu.VMEM(h_struct.shape, h_struct.dtype),
                  pltpu.VMEM(ag_ts.shape, out_dtype),
                  pltpu.SemaphoreType.DMA,   # local staging
                  pltpu.SemaphoreType.DMA,   # rs send
                  pltpu.SemaphoreType.DMA,   # rs recv
                  pltpu.SemaphoreType.DMA,   # ag send
                  pltpu.SemaphoreType.DMA,   # ag recv
                  pltpu.SemaphoreType.REGULAR])  # ag credits
    out, _wsr, _wsa = pl.pallas_call(
        body,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (1 + len(statics)),
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_shape=[
            jax.ShapeDtypeStruct((ag_ts.shape[0] * world,) + ag_ts.shape[1:],
                                 out_dtype),
            jax.ShapeDtypeStruct((world,) + rs_ts.shape, jnp.float32),  # rs ws
            jax.ShapeDtypeStruct((2,) + h_struct.shape, h_struct.dtype),  # ag
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(cid, scratch),
    )(operand, *statics)
    return out


# ---------------------------------------------------------------------------
# Dispatch
# ---------------------------------------------------------------------------

_EMULATED = {
    "ring_ag": _ring_ag_emulated,
    "one_shot_ag": _one_shot_ag_emulated,
    "push_rs": _push_rs_emulated,
    "one_shot_rs": _one_shot_rs_emulated,
    "one_shot_a2a": _one_shot_a2a_emulated,
    "bidir_ring_ag": _bidir_ring_ag_emulated,
    "ring_fold": _ring_fold_emulated,
    "two_level_ag": _two_level_ag_emulated,
    "two_level_rs": _two_level_rs_emulated,
    "push_rs_ring_ag": _push_rs_ring_ag_emulated,
}

_PLTPU = {
    "ring_ag": _ring_ag_pltpu,
    "one_shot_ag": _one_shot_ag_pltpu,
    "push_rs": functools.partial(_rs_pltpu, one_shot=False),
    "one_shot_rs": functools.partial(_rs_pltpu, one_shot=True),
    "one_shot_a2a": _one_shot_a2a_pltpu,
    "bidir_ring_ag": _bidir_ring_ag_pltpu,
    "ring_fold": _ring_fold_pltpu,
    "two_level_ag": _two_level_ag_pltpu,
    "two_level_rs": _two_level_rs_pltpu,
    "push_rs_ring_ag": _push_rs_ring_ag_pltpu,
}


def run(
    protocol: str,
    tile: Optional[Callable],
    operand: Array,
    statics: Sequence[Array] = (),
    *,
    axis: str,
    world: int,
    out_dtype=None,
    collective_id: int = 0,
    backend: Optional[str] = None,
    decode: Optional[Callable] = None,
) -> Array:
    """Execute ``tile`` under a shmem communication protocol.

    ``operand`` is the tensor that moves (AG protocols: the chunk that
    rides/broadcasts; RS protocols: the local tensor whose dim-0 blocks
    produce the pushed partials; one_shot_a2a: a ``(world, ...)`` tensor
    whose block ``t`` is destined for PE ``t``). ``statics`` stay
    rank-resident.
    ``tile=None`` is the identity (pure data movement); ``ring_fold``
    takes a :class:`FoldTile` instead of a pure tile. The two-level
    protocols compose two mesh axes: pass ``axis=(inner, outer)`` and
    ``world=(Wi, Wo)``. ``backend`` is a shmem backend name
    ("pltpu" | "emulated"); default picks per platform
    (``shmem.default_backend``).

    ``decode`` is the RS-side wire hook (push_rs / one_shot_rs only):
    when set, ``tile`` returns a PACKED wire buffer (ops.wire.pack) that
    is pushed verbatim, and ``decode(packed) -> f32`` unpacks each landed
    partial before the owner's reduction. The AG/a2a protocols need no
    hook — the caller packs the riding operand and unpacks inside
    ``tile``, since their payloads pass through workspaces unmodified.
    """
    if protocol not in PROTOCOLS:
        raise ValueError(f"unknown protocol {protocol!r} (not in {PROTOCOLS})")
    two_level = protocol in TWO_LEVEL_PROTOCOLS
    if two_level != isinstance(axis, (tuple, list)):
        raise ValueError(
            f"{protocol}: axis must be {'(inner, outer)' if two_level else 'one axis name'}, got {axis!r}")
    if decode is not None and protocol not in ("push_rs", "one_shot_rs"):
        raise ValueError(
            f"{protocol}: decode is only supported for push_rs/one_shot_rs")
    if two_level:
        axis, world = tuple(axis), tuple(world)
    if protocol == "ring_fold":
        if not isinstance(tile, FoldTile):
            raise ValueError("ring_fold takes a FoldTile (init/fold/finalize)")
    elif protocol == "push_rs_ring_ag":
        if not isinstance(tile, ChainTile):
            raise ValueError("push_rs_ring_ag takes a ChainTile (rs/ag/mid)")
    else:
        tile = tile or _identity
    backend = backend or default_backend()
    impl = (_PLTPU if backend == "pltpu" else _EMULATED)[protocol]
    kwargs = {} if decode is None else {"decode": decode}
    return impl(tile, operand, tuple(statics), axis=axis, world=world,
                out_dtype=out_dtype or operand.dtype, cid=collective_id,
                **kwargs)
