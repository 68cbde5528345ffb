"""Overlapped collective matmuls — compat wrappers + the 2-level ops.

The 1-level ops (``ag_matmul``, ``matmul_rs``, ``all_gather``) are now
DECLARED in :mod:`repro.ops.library` — one tile-level ``OverlapOp`` each,
from which the graph lowering (the ``ag_pipeline``/``rs_pipeline`` folds
of ``core.overlap``), the kernel lowering (the shmem tile executor) and
the dual-op backward are all derived. This module keeps:

  - thin functional wrappers with the historical signatures (callers
    inside ``shard_map`` use these; they delegate to the declared ops
    with no deprecation cost),
  - the hierarchical (Fig. 10) 2-level variants, which compose two mesh
    axes and therefore sit outside the single-axis declaration shape,
  - the stand-alone chunked collectives used by grad sync & decode.

Differentiability is the engine's shared custom_vjp: each declared op's
backward is its DUAL overlapped op (O(1) permute buffers, vs. O(W) for
autodiff of an unrolled ring):

    d(AG+GEMM)/dA = GEMM+RS(g, .)      (dual RS ring)
    d(AG+GEMM)/dB = ring-accumulated A_s^T g_s
    d(GEMM+RS)/dA = AG+GEMM(g, .)      (dual AG ring)
    d(AG)/dx      = ring reduce-scatter
"""
from __future__ import annotations

import jax

from . import overlap as ov

Array = jax.Array


# ---------------------------------------------------------------------------
# Baselines (the NCCL-analogue: monolithic collective, no overlap)
# ---------------------------------------------------------------------------


def ag_matmul_baseline(a_blk: Array, b_loc: Array, axis: str, *, out_dtype=None) -> Array:
    """all_gather(A) @ B with XLA's built-in collective."""
    from ..ops.library import _ag_matmul_baseline

    return _ag_matmul_baseline(a_blk, (b_loc,), axis, out_dtype or a_blk.dtype)


def matmul_rs_baseline(a_loc: Array, b_loc: Array, axis: str, *, out_dtype=None) -> Array:
    """psum_scatter(A @ B) with XLA's built-in collective."""
    from ..ops.library import _matmul_rs_baseline

    return _matmul_rs_baseline(a_loc, (b_loc,), axis, out_dtype or a_loc.dtype)


# ---------------------------------------------------------------------------
# 1-level ops: wrappers over the repro.ops declarations
# ---------------------------------------------------------------------------


def ag_matmul(a_blk, b_loc, axis, *, mode="ring", chunks_per_rank=1,
              out_dtype=None, backend="graph", wire="f32"):
    """Overlapped AllGather-GEMM (see the ``ag_matmul`` declaration in
    ``repro.ops.library``). The backward pass is the dual overlapped
    GEMM+RS ring for BOTH backends — a kernel forward keeps the
    graph-lowered dual as its backward. ``wire`` quantizes the riding
    A-chunks (``repro.ops.wire``)."""
    from .. import ops

    return ops.ag_matmul(a_blk, b_loc, axis=axis, mode=mode,
                         chunks=max(1, chunks_per_rank),
                         out_dtype=out_dtype, backend=backend, wire=wire)


def matmul_rs(a_loc, b_loc, axis, *, mode="ring", chunks_per_rank=1,
              out_dtype=None, backend="graph", wire="f32"):
    """Overlapped GEMM-ReduceScatter; backward = dual AG+GEMM ring.
    ``chunks_per_rank`` (rs_chunks) sub-chunks the ring accumulator into
    column groups; ``backend="kernel"`` lowers through the shmem tile
    executor (ring = Alg. 3 push, one_shot = all partials up-front).
    ``wire`` quantizes the riding partial accumulators."""
    from .. import ops

    return ops.matmul_rs(a_loc, b_loc, axis=axis, mode=mode,
                         chunks=max(1, chunks_per_rank),
                         out_dtype=out_dtype, backend=backend, wire=wire)


def all_gather_chunked(x: Array, axis: str, *, mode: str = "ring",
                       backend: str = "graph", wire: str = "f32") -> Array:
    """Decomposed AllGather; backward = ring reduce-scatter (O(1)).
    ``backend="kernel"`` lowers one_shot through the executor's
    low-latency AllGather protocol."""
    from .. import ops

    return ops.all_gather(x, axis=axis, mode=mode, backend=backend, wire=wire)


# ---------------------------------------------------------------------------
# 2-level (Fig. 10) variants: compound (pod x ring-in-pod) meshes
# ---------------------------------------------------------------------------


def ag_matmul_2level(
    a_blk: Array,
    b_loc: Array,
    inner_axis: str,
    outer_axis: str,
    *,
    mode: str = "two_level",
    out_dtype=None,
    backend: str = "graph",
) -> Array:
    """AG+GEMM over a compound (outer=pod, inner=ring-in-pod) axis — the
    AG dual of ``matmul_rs_2level`` (see the ``ag_matmul_2level``
    declaration in ``repro.ops.library``). Own pod's inner ring runs
    first while peer-pod chunks travel the slow links (Fig. 10's shifted
    start). ``backend="kernel"`` lowers through the executor's two-axis
    ``two_level_ag`` protocol (pod-local one_shot exchange concurrent
    with the inter-pod ring). a_blk: (m_loc, k); returns
    (m_loc * Wo * Wi, n_loc)."""
    from .. import ops

    return ops.ag_matmul_2level(a_blk, b_loc, axis=(inner_axis, outer_axis),
                                mode=mode, out_dtype=out_dtype,
                                backend=backend)


def matmul_rs_2level(
    a_loc: Array,
    b_loc: Array,
    inner_axis: str,
    outer_axis: str,
    *,
    mode: str = "two_level",
    out_dtype=None,
    backend: str = "graph",
) -> Array:
    """GEMM+RS over a compound (outer=pod, inner=ring-in-pod) axis
    (Fig. 10 / Alg. 5; the ``matmul_rs_2level`` declaration in
    ``repro.ops.library``). ``backend="kernel"`` lowers through the
    executor's ``two_level_rs`` protocol. a_loc: (m, k_loc) with K
    sharded over outer*inner; returns (m / (Wo*Wi), n)."""
    from .. import ops

    return ops.matmul_rs_2level(a_loc, b_loc, axis=(inner_axis, outer_axis),
                                mode=mode, out_dtype=out_dtype,
                                backend=backend)


# The 2-level ops and "reduce_scatter" are DECLARED in repro.ops.library
# (two_level_ag/two_level_rs executor protocols; f32-accumulating tile
# over the RS pipelines + push_rs/one_shot_rs kernel protocols).


# ---------------------------------------------------------------------------
# Chunked stand-alone collectives (used by grad sync & decode paths)
# ---------------------------------------------------------------------------


def reduce_scatter_chunked(x: Array, axis: str, *, mode: str = "ring",
                           backend: str = "graph", wire: str = "f32") -> Array:
    """Decomposed reduce-scatter along dim 0 (accumulator in f32); see
    the ``reduce_scatter`` declaration in ``repro.ops.library``.
    ``backend="kernel"`` lowers ring through the executor's Alg.-3 push
    and one_shot through the all-partials-up-front protocol. ``wire``
    quantizes the riding partials (decoded + accumulated in f32)."""
    from .. import ops

    return ops.reduce_scatter(x, axis=axis, mode=mode, backend=backend,
                              wire=wire)


def hierarchical_reduce_scatter(x: Array, inner_axis: str, outer_axis: str) -> Array:
    """RS along inner (fast links), then ring all-reduce along outer (slow
    links) on the already 1/Wi-sized shard — the gradient-sync pattern."""
    shard = reduce_scatter_chunked(x, inner_axis)
    return ov.ring_allreduce(shard, outer_axis)


def hierarchical_all_gather(x: Array, inner_axis: str, outer_axis: str) -> Array:
    """Inverse of hierarchical RS: gather along inner axis only (params are
    replicated across pods, sharded within)."""
    return all_gather_chunked(x, inner_axis)


# ---------------------------------------------------------------------------
# shard_map wrappers (for tests / standalone use)
# ---------------------------------------------------------------------------


def make_sharded(fn, mesh, in_specs, out_specs):
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False)
    )


# Importing this module must populate the full registry (tests and the
# tuner enumerate it); the 1-level declarations live in repro.ops.
from .. import ops as _ops  # noqa: E402,F401
