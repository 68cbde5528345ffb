"""Overlapped MoE communication (paper: AG+MoE, MoE+RS, low-latency
AllToAll), declared over the ring-pipeline engine (``core.overlap``).

Two parallelism modes, matching the paper's coverage:

  TP MoE (FLUX-style, the paper's AG+MoE / MoE+RS kernels): every rank
  holds a d_ff-shard of EVERY expert. Tokens are sequence-sharded; the
  layer rides token chunks on the engine's AG transports (ring / bidir /
  one_shot) and runs the grouped GEMM per chunk as it arrives (Fig. 7
  swizzle), then combines and Reduce-Scatters the outputs chunk-by-chunk
  (Alg. 3 / the engine's RS transports).

  EP MoE (DeepEP-style, the paper's AllToAll dispatch/combine): experts
  are sharded across ranks; tokens travel to their experts via the
  engine's a2a_pipeline (one_shot = all transfers issued up-front — the
  low-latency structure of the paper's inference AllToAll), compute runs
  per-arrival, and a second AllToAll brings results home.

Dispatch is capacity-based (dense (E, cap, d) buffers) so the expert GEMM
is a regular grouped matmul — the TPU-native substitute for ragged
grouping. Registry entries: "ag_moe", "moe_rs", "a2a_ep".
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from . import overlap as ov

Array = jax.Array


class DispatchInfo(NamedTuple):
    expert: Array  # (T, k) expert id per token-slot
    position: Array  # (T, k) position within the expert's capacity buffer
    weight: Array  # (T, k) combine weight (renormalized top-k prob, 0 if dropped)


def topk_route(logits: Array, k: int):
    """The router's choice: logits (T, E) -> (expert ids (T, k), their
    softmax probabilities renormalised to sum to 1 (T, k)). A token's k
    experts are distinct."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, k)  # (T, k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def dispatch(x: Array, top_e: Array, top_p: Array, e: int, capacity: int):
    """Capacity-based dispatch of the routed tokens x (T, d) into ``e``
    expert buffers of ``capacity`` rows: (dispatched (E, cap, d),
    DispatchInfo). A token past its expert's capacity is dropped
    (weight 0), standard capacity-factor routing; a capacity of T drops
    none."""
    t, d = x.shape
    k = top_e.shape[1]
    onehot = jax.nn.one_hot(top_e, e, dtype=jnp.int32)  # (T, k, E)
    flat = onehot.reshape(t * k, e)  # slot-major: token 0 slot 0, token 0 slot 1, ...
    pos_in_expert = jnp.cumsum(flat, axis=0) - flat  # (T*k, E)
    pos = jnp.sum(pos_in_expert * flat, axis=-1).reshape(t, k)  # (T, k)
    keep = pos < capacity
    weight = jnp.where(keep, top_p, 0.0)
    pos_c = jnp.where(keep, pos, capacity - 1)

    disp = jnp.zeros((e, capacity, d), x.dtype)
    xk = jnp.broadcast_to(x[:, None, :], (t, k, d))
    mask = keep[..., None].astype(x.dtype)
    disp = disp.at[top_e.reshape(-1), pos_c.reshape(-1)].add(
        (xk * mask).reshape(t * k, d), mode="drop"
    )
    return disp, DispatchInfo(top_e, pos_c, weight)


def topk_combine(out: Array, info: DispatchInfo, out_dtype=None) -> Array:
    """Inverse of dispatch: (E, cap, d), info -> (T, d)."""
    t, k = info.expert.shape
    gathered = out[info.expert.reshape(-1), info.position.reshape(-1)]  # (T*k, d)
    gathered = gathered.reshape(t, k, -1).astype(jnp.float32)
    y = jnp.sum(gathered * info.weight[..., None], axis=1)
    return y.astype(out_dtype or out.dtype)


# ---------------------------------------------------------------------------
# EP AllToAll — the declared "a2a_ep" op (repro.ops.library): graph =
# engine a2a_pipeline (one_shot low-latency / XLA baseline), kernel =
# the executor's one_shot_a2a push protocol. Both directions are the
# SAME registered op; the inverse just transposes block placement.
# ---------------------------------------------------------------------------


def a2a_ep(x: Array, axis: str, *, mode: str = "one_shot",
           backend: str = "graph", wire: str = "f32") -> Array:
    """Expert-parallel AllToAll.

    x: (E_global, cap, d) where E_global = W * E_local; rank r keeps the
    slab for the experts it owns: returns (E_local, W * cap, d) — every
    rank's tokens for my local experts. ``wire`` quantizes the riding
    token slabs (see ``repro.ops.wire``).
    """
    w = lax.axis_size(axis)
    e_global, cap, d = x.shape
    e_local = e_global // w
    mode = ov.resolve_mode("a2a_ep", mode)
    xs = x.reshape(w, e_local, cap, d)  # block t = my tokens for rank t's experts
    y = ov.dispatch("a2a_ep", xs, axis=axis, mode=mode, backend=backend,
                    wire=ov.resolve_wire("a2a_ep", wire, mode))
    # y[src] = rank src's tokens for my experts
    return jnp.moveaxis(y, 0, 1).reshape(e_local, w * cap, d)


def a2a_ep_inverse(y: Array, axis: str, *, mode: str = "one_shot",
                   backend: str = "graph", wire: str = "f32") -> Array:
    """Inverse AllToAll: (E_local, W*cap, d) -> (E_global, cap, d)."""
    w = lax.axis_size(axis)
    e_local, wc, d = y.shape
    cap = wc // w
    mode = ov.resolve_mode("a2a_ep", mode)
    ys = jnp.moveaxis(y.reshape(e_local, w, cap, d), 1, 0)  # (W, e_local, cap, d)
    x = ov.dispatch("a2a_ep", ys, axis=axis, mode=mode, backend=backend,
                    wire=ov.resolve_wire("a2a_ep", wire, mode))
    return x.reshape(w * e_local, cap, d)


# ---------------------------------------------------------------------------
# TP MoE: AG + GroupGEMM and GroupGEMM + RS (the paper's fused MoE ops)
# ---------------------------------------------------------------------------


def ag_moe(
    x_blk: Array,  # (T_loc, d) sequence-sharded tokens
    logits_blk: Array,  # (T_loc, E) their router logits
    expert_fn,  # (tokens (T_loc,d), logits (T_loc,E)) -> (T_loc, d_out)
    axis: str,
    *,
    mode: str = "ring",
    backend: str = "graph",
) -> Array:
    """AllGather-MoE overlap: token chunks ride the engine transport; the
    (d_ff-sharded) expert computation runs on each chunk as it arrives;
    every rank produces the full sequence's partial outputs (to be
    reduced by rs afterwards or combined directly when expert_fn output
    is complete). ``backend="kernel"`` lowers through the shmem tile
    executor (tokens+logits packed into one riding chunk)."""
    return ov.dispatch("ag_moe", x_blk, logits_blk, axis=axis,
                       mode=ov.resolve_mode("ag_moe", mode), backend=backend,
                       expert_fn=expert_fn)


def _ag_moe_graph(static, x_blk, logits_blk):
    """Engine (lax.ppermute) lowering of ag_moe.

    Assembly avoids a dynamic_update_slice chain (whose autodiff keeps
    all W buffer versions live in the backward): chunks are collected in
    computation order and realigned with ONE static concat + ONE cyclic
    roll per direction (an O(1)-buffer transpose).
    """
    axis, mode, expert_fn = static["axis"], static["mode"], static["expert_fn"]
    if mode == "none":
        # monolithic baseline: gather everything, then one big expert pass
        return expert_fn(
            lax.all_gather(x_blk, axis, tiled=True),
            lax.all_gather(logits_blk, axis, tiled=True),
        )
    w = lax.axis_size(axis)
    me = lax.axis_index(axis)
    t_loc = x_blk.shape[0]

    if mode == "bidir" and t_loc % 2 == 0 and w >= 3:
        h = t_loc // 2

        def fold2(carry, bufs, s, owner, direction):
            ys_f, ys_b = carry
            y = expert_fn(bufs[0], bufs[1])
            return (ys_f + [y], ys_b) if direction == 0 else (ys_f, ys_b + [y])

        ys_f, ys_b = ov.bidir_ag_pipeline((x_blk, logits_blk), fold2, ([], []), axis)
        d_out = ys_f[0].shape[-1]
        # forward halves: owners me, me-1, ... -> reversed is ascending
        # cyclically from me+1; backward halves: owners me, me+1, ... are
        # already ascending from me.
        tops = jnp.roll(jnp.concatenate(ys_f[::-1], 0), (me + 1) * h, axis=0)
        bots = jnp.roll(jnp.concatenate(ys_b, 0), me * h, axis=0)
        out = jnp.concatenate(
            [tops.reshape(w, h, d_out), bots.reshape(w, h, d_out)], axis=1
        )
        return out.reshape(w * t_loc, d_out)

    if mode == "bidir":
        mode = "ring"

    def fold(ys, bufs, s, owner):
        return ys + [expert_fn(bufs[0], bufs[1])]  # chunk of owner (me - s) % w

    ys = ov.ag_pipeline((x_blk, logits_blk), fold, [], axis, transport=mode)
    rev = jnp.concatenate(ys[::-1], axis=0)
    return jnp.roll(rev, shift=(me + 1) * t_loc, axis=0)


def moe_rs(
    x_full: Array,  # (T, d) full gathered tokens
    logits_full: Array,  # (T, E)
    expert_fn,  # partial-output expert computation (d_ff-sharded)
    axis: str,
    *,
    mode: str = "ring",
    backend: str = "graph",
) -> Array:
    """GroupGEMM-ReduceScatter overlap (paper MoE+RS): the expert output
    block destined for each rank is the rs_pipeline's per-block compute;
    the accumulator rides the engine transport (Alg. 3 schedule, plus
    bidir token-halves and the one_shot low-latency variant).
    ``backend="kernel"`` lowers ring through the executor's Alg.-3 push
    and one_shot through the all-partials-up-front protocol."""
    return ov.dispatch("moe_rs", x_full, logits_full, axis=axis,
                       mode=ov.resolve_mode("moe_rs", mode), backend=backend,
                       expert_fn=expert_fn)


def _moe_rs_graph(static, x_full, logits_full):
    """Engine (lax.ppermute) lowering of moe_rs."""
    axis, mode, expert_fn = static["axis"], static["mode"], static["expert_fn"]
    if mode == "none":
        # monolithic baseline: full expert pass, then XLA's reduce-scatter
        partial = expert_fn(x_full, logits_full).astype(jnp.float32)
        return lax.psum_scatter(
            partial, axis, scatter_dimension=0, tiled=True
        ).astype(x_full.dtype)
    w = lax.axis_size(axis)
    t = x_full.shape[0]
    t_blk = t // w

    def rows(start, size):
        xb = lax.dynamic_slice(x_full, (start, 0), (size, x_full.shape[1]))
        lb = lax.dynamic_slice(logits_full, (start, 0), (size, logits_full.shape[1]))
        return xb, lb

    if mode == "bidir" and t_blk % 2 == 0 and w >= 3:
        h = t_blk // 2

        def compute2(blk, s, direction):
            xb, lb = rows(blk * t_blk + direction * h, h)
            return expert_fn(xb, lb).astype(jnp.float32)

        acc_f, acc_r = ov.bidir_rs_pipeline(compute2, axis)
        return jnp.concatenate([acc_f, acc_r], axis=0).astype(x_full.dtype)

    if mode == "bidir":
        mode = "ring"

    def compute(blk, s):
        xb, lb = rows(blk * t_blk, t_blk)
        return expert_fn(xb, lb).astype(jnp.float32)

    return ov.rs_pipeline(compute, axis, transport=mode).astype(x_full.dtype)


# ---------------------------------------------------------------------------
# Kernel (shmem tile executor) lowerings: tokens and logits are packed
# into ONE riding chunk (the executor protocols move a single operand),
# and the tile unpacks the columns before calling expert_fn. The expert
# closure arrives per call in the static dict — these ops sit outside
# the declarative library only because their compute is a caller-
# supplied closure, not a declaration-time tile.
# ---------------------------------------------------------------------------

_AG_MOE_CID, _MOE_RS_CID = 24, 25
_AG_MOE_PROTOS = {"ring": "ring_ag", "bidir": "bidir_ring_ag",
                  "one_shot": "one_shot_ag"}
_MOE_RS_PROTOS = {"ring": "push_rs", "one_shot": "one_shot_rs"}


def _moe_pack(x: Array, logits: Array) -> Array:
    # pack in the PROMOTED dtype so neither side loses precision on the
    # wire (bf16 tokens + f32 router logits -> f32 packed; the unpack
    # cast back to each original dtype is then exact)
    pdt = jnp.promote_types(x.dtype, logits.dtype)
    return jnp.concatenate([x.astype(pdt), logits.astype(pdt)], axis=1)


def _moe_tile(expert_fn, d: int, x_dtype, logits_dtype):
    def tile(packed):
        return expert_fn(packed[:, :d].astype(x_dtype),
                         packed[:, d:].astype(logits_dtype))

    return tile


def _ag_moe_kernel(static, x_blk, logits_blk):
    from ..shmem import executor

    axis = static["axis"]
    tile = _moe_tile(static["expert_fn"], x_blk.shape[1], x_blk.dtype,
                     logits_blk.dtype)
    packed = _moe_pack(x_blk, logits_blk)
    out_dtype = jax.eval_shape(tile, packed).dtype
    return executor.run(
        _AG_MOE_PROTOS[static["mode"]], tile, packed, axis=axis,
        world=lax.axis_size(axis), out_dtype=out_dtype,
        collective_id=_AG_MOE_CID)


def _moe_rs_kernel(static, x_full, logits_full):
    from ..shmem import executor

    axis = static["axis"]
    tile = _moe_tile(static["expert_fn"], x_full.shape[1], x_full.dtype,
                     logits_full.dtype)
    # out_dtype=f32: partials ride and reduce in f32, matching the graph
    # lowering's f32 accumulator; the final cast happens here, once.
    acc = executor.run(
        _MOE_RS_PROTOS[static["mode"]], tile, _moe_pack(x_full, logits_full),
        axis=axis, world=lax.axis_size(axis), out_dtype=jnp.float32,
        collective_id=_MOE_RS_CID)
    return acc.astype(x_full.dtype)


# ---------------------------------------------------------------------------
# Derived backwards: jax.vjp OF THE EXPERT CLOSURE. The expert is a
# caller closure with no declared tile (it may be nonlinear AND
# rank-dependent), so the authoring API's linear-tile duals do not
# apply; instead each rank differentiates ITS OWN closure at the true
# primal chunks and the cotangents ride the dual schedules. Routing
# through the shared custom_vjp is what lets the TRAIN path use the
# KERNEL lowering: the kernel forward keeps this graph-schedule dual as
# its backward (autodiff cannot go through the io_callback kernel fwd).
# ---------------------------------------------------------------------------


def _ag_moe_bwd(static, res, g):
    """d(ag_moe): stack-gather the packed token|logit chunks once (ONE
    residual ring — the same packed riding chunk the kernel forward
    uses), vjp the local expert at every owner's chunk against that
    owner's output-row cotangents, then reduce the packed
    (d_tokens | d_logits) partials home on the dual RS ring."""
    axis, expert_fn = static["axis"], static["expert_fn"]
    x_blk, l_blk = res
    t_loc = x_blk.shape[0]
    d = x_blk.shape[1]
    stacked = ov.stack_gather_pipeline(_moe_pack(x_blk, l_blk), axis,
                                       transport="ring")

    def contrib(blk, s):
        del s
        chunk = lax.dynamic_index_in_dim(stacked, blk, 0, keepdims=False)
        xb = chunk[:, :d].astype(x_blk.dtype)  # exact unpack casts
        lb = chunk[:, d:].astype(l_blk.dtype)
        g_blk = lax.dynamic_slice(g, (blk * t_loc, 0), (t_loc, g.shape[1]))
        _, vjp = jax.vjp(expert_fn, xb, lb)
        dxb, dlb = vjp(g_blk)
        return jnp.concatenate(
            [dxb.astype(jnp.float32), dlb.astype(jnp.float32)], axis=1)

    packed = ov.rs_pipeline(contrib, axis, transport="ring")
    return (packed[:, :d].astype(x_blk.dtype),
            packed[:, d:].astype(l_blk.dtype))


def _moe_rs_bwd(static, res, g):
    """d(moe_rs): ONE dual AG ring of the per-rank output-block
    cotangents; each arriving g block is pushed back through this rank's
    expert closure at the true local primal rows (f32 accumulation,
    matching the forward's accumulator dtype)."""
    axis, expert_fn = static["axis"], static["expert_fn"]
    x_full, l_full = res
    w = lax.axis_size(axis)
    t_blk = x_full.shape[0] // w

    def rows(t, start):
        return lax.dynamic_slice(t, (start, 0), (t_blk, t.shape[1]))

    def fold(carry, bufs, s, owner):
        del s
        dx, dl = carry
        xb = rows(x_full, owner * t_blk)
        lb = rows(l_full, owner * t_blk)
        _, vjp = jax.vjp(
            lambda a, b: expert_fn(a, b).astype(jnp.float32), xb, lb)
        gxb, glb = vjp(bufs[0].astype(jnp.float32))
        dx = lax.dynamic_update_slice(dx, gxb.astype(jnp.float32),
                                      (owner * t_blk, 0))
        dl = lax.dynamic_update_slice(dl, glb.astype(jnp.float32),
                                      (owner * t_blk, 0))
        return dx, dl

    init = (jnp.zeros(x_full.shape, jnp.float32),
            jnp.zeros(l_full.shape, jnp.float32))
    dx, dl = ov.ag_pipeline((g,), fold, init, axis, transport="ring")
    return dx.astype(x_full.dtype), dl.astype(l_full.dtype)


# ---------------------------------------------------------------------------
# Registry entries. The "a2a_ep" entry is DECLARED in repro.ops.library
# (one_shot_a2a kernel protocol + self-dual backward); the trailing
# import below guarantees the declaration runs for anyone importing this
# module directly.
# ---------------------------------------------------------------------------

ov.register("ag_moe", kind="ag", transports=("ring", "bidir", "one_shot"),
            baseline="none", default="ring", fwd=_ag_moe_graph,
            bwd=_ag_moe_bwd,
            kernel_transports=("ring", "bidir", "one_shot"),
            kernel_fwd=_ag_moe_kernel)
ov.register("moe_rs", kind="rs", transports=("ring", "bidir", "one_shot"),
            baseline="none", default="ring", fwd=_moe_rs_graph,
            bwd=_moe_rs_bwd,
            kernel_transports=("ring", "one_shot"),
            kernel_fwd=_moe_rs_kernel)

from .. import ops as _ops  # noqa: E402,F401  (registers a2a_ep et al.)
