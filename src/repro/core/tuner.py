"""Distributed autotuner (paper §3.8): analytic model + whole-step profiler.

Analytic mode — the TPU analogue of the paper's resource-partition
arithmetic (§3.5: "if local reduction exceeds 470 GB/s, perfect overlap").
On TPU the partition knob is temporal (chunk count/size), so the model
answers: for a given overlapped op, which (mode, chunks_per_rank) makes
per-step DMA time <= per-step MXU time, minimizing the critical path

    T = fill_bubble + sum_steps max(t_compute_step, t_comm_step).

Empirical mode — the paper's distributed-tuning protocol: overlapped
kernels synchronize through signals, so a naive repeat-the-kernel
profiler would deadlock or skew (signals must be reset between runs).
The tuner therefore times a USER-WRAPPED step function as a whole, one
candidate config per iteration, with an explicit reset callback, then
selects the globally best config (all ranks see the same argmin since
timing happens on the host driving the SPMD program).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, Optional, Sequence

import jax

from .. import hw
from ..ops import wire as wirefmt
from . import overlap, schedules


@dataclass(frozen=True)
class OverlapChoice:
    mode: str  # a transport from the engine registry, or the op baseline
    chunks_per_rank: int
    # analytic estimates (seconds) for the roofline log
    t_compute: float
    t_comm: float
    t_total: float
    wire: str = "f32"  # riding-chunk wire dtype (registry wires axis)
    placement: str = "contiguous"  # chunk->rank row placement (registry axis)


def _dot_time(m: float, k: float, n: float, spec: hw.HardwareSpec, eff: float = 0.6) -> float:
    return 2.0 * m * k * n / (spec.peak_flops_bf16 * eff)


def _codec_time(rows: int, cols: int, spec: hw.HardwareSpec) -> float:
    """Per-chunk cost of the wire codec: one encode + one decode pass,
    each streaming the f32 view of the chunk through HBM. This is the
    term that keeps quantization from being a free lunch — when the op
    is compute-bound, the extra passes make a low-precision wire
    strictly WORSE, so the enumeration only picks int8/fp8 where the
    ICI-bytes term actually binds."""
    return 2.0 * rows * cols * 4 / spec.hbm_bandwidth


def analytic_ag_matmul(
    m_loc: int,
    k: int,
    n_loc: int,
    world: int,
    *,
    dtype_bytes: int = 2,
    spec: Optional[hw.HardwareSpec] = None,
    candidates: Optional[Sequence[str]] = None,
    max_sub: int = 4,
) -> OverlapChoice:
    """Pick the overlap strategy for AllGather-GEMM.

    Candidates default to the engine registry's ag_matmul transports
    (baseline included) — adding a transport to the registry
    automatically enrolls it here.

    Per ring step: compute = dot(m_loc, k, n_loc); comm = ship one chunk
    (m_loc * k * bytes) over one link (ring) or both directions (bidir).
    one_shot: all (W-1) chunks in flight at once across the torus links —
    bandwidth-limited by links/chip, latency-optimal for small messages.

    The wire axis is enumerated jointly with mode x chunks: for every
    non-baseline mode, each registry wire dtype for ag_matmul scales the
    riding-chunk bytes (``ops.wire.wire_bytes`` — payload + per-row
    scales) and charges the codec passes to the compute side.
    """
    spec = spec or hw.local_spec()
    if candidates is None:
        candidates = overlap.transports_for("ag_matmul", include_baseline=True)
    f32_bytes = m_loc * k * dtype_bytes
    t_dot = _dot_time(m_loc, k, n_loc, spec)
    t_cod = _codec_time(m_loc, k, spec)
    best: Optional[OverlapChoice] = None
    for mode in candidates:
        if mode == "none":
            subs = (1,)
        elif mode == "ring":
            subs = tuple(s for s in range(1, max_sub + 1) if m_loc % s == 0)
        elif mode == "bidir":
            subs = (1,) if m_loc % 2 == 0 and world >= 3 else ()
        elif mode == "one_shot":
            subs = (1,)
        else:
            continue
        wires = ("f32",) if mode == "none" else overlap.wires_for("ag_matmul")
        for wname in wires:
            chunk_bytes = wirefmt.wire_bytes(m_loc, k, wname, dtype_bytes)
            cod = 0.0 if wname == "f32" else t_cod
            t_step = t_dot + cod  # per-chunk MXU time + codec passes
            for sub in subs:
                if mode == "none":
                    t_comm = (world - 1) * chunk_bytes / spec.ici_link_bandwidth
                    t_comp = world * t_step
                    t_total = t_comm + t_comp  # serialized: collective then GEMM
                elif mode == "ring":
                    # per-message fixed overhead is what caps useful sub-
                    # chunking: finer chunks shrink the fill bubble but pay
                    # the hop/descriptor cost world*sub times
                    t_step_comm = (chunk_bytes / sub) / spec.ici_link_bandwidth \
                        + spec.ici_msg_overhead
                    t_step_comp = t_step / sub
                    fill = t_step_comm  # first remote chunk latency
                    t_comm = (world - 1) * chunk_bytes / spec.ici_link_bandwidth
                    t_comp = world * t_step
                    t_total = fill + world * sub * max(t_step_comm, t_step_comp)
                elif mode == "bidir":
                    t_step_comm = (chunk_bytes / 2) / spec.ici_link_bandwidth
                    t_step_comp = t_step
                    t_comm = (world - 1) * chunk_bytes / (2 * spec.ici_link_bandwidth)
                    t_comp = world * t_step
                    t_total = t_step_comm + world * max(t_step_comm, t_step_comp)
                else:  # one_shot
                    total_bytes = (world - 1) * chunk_bytes
                    t_comm = total_bytes / (spec.ici_link_bandwidth * spec.ici_links)
                    t_comp = world * t_step
                    # local chunk computes during the flight of everything else
                    t_total = max(t_comm, t_step) + (world - 1) * t_step
                cand = OverlapChoice(mode, sub if mode == "ring" else 1,
                                     t_comp, t_comm, t_total, wname)
                if best is None or cand.t_total < best.t_total:
                    best = cand
    if best is None:
        # every candidate was infeasible (e.g. bidir with odd m_loc):
        # mirror the engine, which degrades such requests to ring
        t_step_comm = f32_bytes / spec.ici_link_bandwidth
        best = OverlapChoice(
            "ring", 1, world * t_dot,
            (world - 1) * t_step_comm,
            t_step_comm + world * max(t_step_comm, t_dot),
        )
    return best


def analytic_matmul_rs(
    m: int,
    k_loc: int,
    n: int,
    world: int,
    *,
    dtype_bytes: int = 2,
    spec: Optional[hw.HardwareSpec] = None,
    candidates: Optional[Sequence[str]] = None,
    max_sub: int = 4,
) -> OverlapChoice:
    """Pick the overlap strategy for GEMM-ReduceScatter. Candidates
    default to the engine registry's matmul_rs transports (baseline
    included).

    ring also enumerates ``rs_chunks`` sub-chunking (the accumulator
    split into column groups, mirroring ag_chunks): sub-chunking shrinks
    the first-message fill bubble at the cost of more, smaller permutes.

    The wire axis rides the same enumeration: a low-precision wire
    shrinks the riding f32 accumulator to payload + per-row scales but
    pays encode+decode passes EVERY hop (the ring re-encodes the
    accumulator each step), so it only wins where the ICI term binds.
    """
    spec = spec or hw.local_spec()
    if candidates is None:
        candidates = overlap.transports_for("matmul_rs", include_baseline=True)
    m_blk = m // world
    t_dot = _dot_time(m_blk, k_loc, n, spec)
    acc_bytes = m_blk * n * 4  # f32 accumulator rides the ring
    f32_step_comm = acc_bytes / spec.ici_link_bandwidth
    t_cod = _codec_time(m_blk, n, spec)
    t_comp = world * t_dot
    t_comm = (world - 1) * f32_step_comm
    best: Optional[OverlapChoice] = None
    for mode in candidates:
        if mode == "ring":
            subs = tuple(s for s in range(1, max_sub + 1) if n % s == 0)
        else:
            subs = (1,)
        wires = ("f32",) if mode == "none" else overlap.wires_for("matmul_rs")
        for wname in wires:
            ride_bytes = wirefmt.wire_bytes(m_blk, n, wname, 4)
            t_step_comm = ride_bytes / spec.ici_link_bandwidth
            cod = 0.0 if wname == "f32" else t_cod
            t_step = t_dot + cod  # per-hop MXU time + codec passes
            for sub in subs:
                if mode == "none":
                    # serialized: all dots, then the monolithic reduce-scatter
                    t_total = t_comp + t_comm
                elif mode == "ring":
                    # sub column-groups: each ring step moves ride_bytes/sub
                    # per group (fill = one sub-message flight), paying the
                    # fixed per-message cost world*sub times — the trade-off
                    # that keeps the enumeration from degenerating to max_sub
                    t_sub_comm = t_step_comm / sub + spec.ici_msg_overhead
                    t_total = t_sub_comm + world * sub * max(t_step / sub, t_sub_comm)
                elif mode == "bidir":
                    if world < 3:
                        continue
                    # half the accumulator columns per direction, both links busy
                    t_total = t_step_comm / 2 + world * max(t_step, t_step_comm / 2)
                elif mode == "one_shot":
                    # W-1 full partials in flight at once across all links: latency
                    # optimal, bandwidth hungry ((W-1)x the wire bytes of ring's
                    # steady state per link); each partial is encoded once and
                    # decoded once on arrival
                    t_total = world * t_step + (world - 1) * ride_bytes / (
                        spec.ici_link_bandwidth * spec.ici_links
                    )
                else:
                    continue
                cand = OverlapChoice(mode, sub if mode == "ring" else 1,
                                     world * t_step, t_comm, t_total, wname)
                if best is None or cand.t_total < best.t_total:
                    best = cand
    if best is None:
        # every candidate was infeasible (e.g. bidir with world < 3):
        # mirror the engine, which degrades such requests to ring
        t_total = f32_step_comm + world * max(t_dot, f32_step_comm)
        best = OverlapChoice("ring", 1, t_comp, t_comm, t_total)
    return best


def causal_flop_fraction(placement: str, world: int, s_loc: int) -> float:
    """CRITICAL-PATH fraction of the dense blockwise-attention FLOPs a
    causal mask leaves live, per placement: ``max_r causal_pairs(r) /
    (s_loc * S)``. Contiguous concentrates the late (expensive) rows on
    the last rank — its fraction approaches 1 as world grows — while
    zigzag gives every rank one early + one late half-chunk (fraction
    ~1/2, rank-independent) and striped interleaves rows round-robin
    (~1/2 + 1/(2*s_loc)). The ring is lockstep, so the slowest rank IS
    the step time: this maximum is the term the analytic model charges.
    """
    total = s_loc * s_loc * world
    return max(
        schedules.causal_pairs(placement, world, r, s_loc)
        for r in range(world)) / float(total)


def analytic_ring_attention(
    s_loc: int,
    d: int,
    world: int,
    *,
    causal: bool = True,
    heads: int = 1,
    dtype_bytes: int = 2,
    spec: Optional[hw.HardwareSpec] = None,
    candidates: Optional[Sequence[str]] = None,
    placements: Optional[Sequence[str]] = None,
) -> OverlapChoice:
    """Pick (mode, wire, placement) for causal/non-causal ring attention.

    Per ring step: compute = one blockwise-attention tile (QK^T + PV:
    ``4 * s_loc^2 * d`` FLOPs per head); comm = ship one packed K|V
    chunk (``s_loc * 2d * bytes`` per KV head). The causal model charges
    the TRUE per-rank live-FLOP fraction per placement
    (:func:`causal_flop_fraction`): under contiguous the last rank owns
    the most-attended rows, so the lockstep critical path stays ~dense,
    while zigzag/striped cut it toward 1/2 — the interior optimum that
    makes the placement axis worth enumerating. Non-causal placements
    are FLOP-identical, so the enumeration keeps contiguous (strict-<
    selection, contiguous first).
    """
    spec = spec or hw.local_spec()
    if candidates is None:
        candidates = overlap.transports_for("ring_attention",
                                            include_baseline=False)
    if placements is None:
        placements = overlap.placements_for("ring_attention")
    t_blk = 2.0 * heads * _dot_time(s_loc, d, s_loc, spec)  # QK^T + PV
    t_cod = _codec_time(s_loc, 2 * d, spec)
    best: Optional[OverlapChoice] = None
    for placement in placements:
        if placement == "zigzag" and s_loc % 2:
            continue  # the engine degrades odd-s_loc zigzag to contiguous
        frac = causal_flop_fraction(placement, world, s_loc) if causal \
            else 1.0
        t_step = t_blk * frac
        for mode in candidates:
            wires = overlap.wires_for("ring_attention")
            for wname in wires:
                chunk_bytes = wirefmt.wire_bytes(s_loc, 2 * d, wname,
                                                 dtype_bytes)
                cod = 0.0 if wname == "f32" else t_cod
                if mode == "ring":
                    t_step_comm = chunk_bytes / spec.ici_link_bandwidth \
                        + spec.ici_msg_overhead
                    t_total = t_step_comm + world * max(
                        t_step_comm, t_step + cod)
                elif mode == "one_shot":
                    t_comm_all = (world - 1) * chunk_bytes / (
                        spec.ici_link_bandwidth * spec.ici_links)
                    t_total = max(t_comm_all, t_step + cod) \
                        + (world - 1) * (t_step + cod)
                else:
                    continue
                cand = OverlapChoice(
                    mode, 1, world * (t_step + cod),
                    (world - 1) * chunk_bytes / spec.ici_link_bandwidth,
                    t_total, wname, placement)
                if best is None or cand.t_total < best.t_total:
                    best = cand
    if best is None:
        t_step_comm = s_loc * 2 * d * dtype_bytes / spec.ici_link_bandwidth
        best = OverlapChoice("ring", 1, world * t_blk,
                             (world - 1) * t_step_comm,
                             t_step_comm + world * max(t_step_comm, t_blk))
    return best


def recommend_backend(modes: Optional[Dict[str, str]] = None) -> str:
    """Lowering backend for the current platform (the backend axis of the
    registry, enumerated alongside the transport candidates).

    On real TPU the fused shmem kernels ("kernel") remove the per-step
    XLA dispatch between chunk compute and chunk DMA, so they are the
    default whenever the chosen mode has a kernel lowering for at least
    one op. On CPU the emulated-DMA backend is a correctness vehicle
    (host callbacks), not a fast path — recommend "graph".
    ``ParallelConfig.backend_for`` re-clamps per op, so emitting
    "kernel" is safe even when only some ops support it.
    """
    import jax

    if jax.default_backend() != "tpu":
        return "graph"
    modes = modes or {}
    for op, mode in modes.items():
        spec = overlap.registry().get(op)
        if spec is not None and mode in spec.kernel_transports:
            return "kernel"
    return "graph" if modes else "kernel"


def recommend_overlap_modes(
    m: int,
    k: int,
    n: int,
    world: int,
    *,
    dtype_bytes: int = 2,
    spec: Optional[hw.HardwareSpec] = None,
):
    """Analytic :class:`repro.ops.OverlapPolicy` for a layer with GLOBAL
    GEMM dims (m, k, n) sharded over ``world`` TP ranks — drop it
    straight onto ``ParallelConfig.overlap`` (``launch/steps.default_pcfg``
    does, under ``overlap_mode="auto"``; no dict re-packing anywhere).

    The per-op mode map carries the analytic AG/RS picks plus the
    latency-bound ops' registry defaults (a2a_ep, flash_decode stay
    one_shot — their message sizes do not depend on the layer dims the
    analytic model sees); the chunk knobs are the enumerated sub-chunk
    winners; the backend is the lowering recommendation
    (:func:`recommend_backend`).
    """
    spec = spec or hw.local_spec()
    from ..ops.policy import LATENCY_OPS, OverlapPolicy

    ag = analytic_ag_matmul(max(1, m // world), k, max(1, n // world), world,
                            dtype_bytes=dtype_bytes, spec=spec)
    rs = analytic_matmul_rs(m, max(1, k // world), n, world,
                            dtype_bytes=dtype_bytes, spec=spec)
    modes = dict(LATENCY_OPS)
    modes.update({"ag_matmul": ag.mode, "matmul_rs": rs.mode})
    # the carry-passing / compound-mesh ops enumerate too (kernel-capable
    # since the ring_fold / two_level executor protocols): ring attention
    # follows the AG regime pick clamped to its transports — its K/V
    # chunks ride exactly the AG data path — and the 2-level ops have a
    # single (two_level) transport.
    modes["ring_attention"] = overlap.resolve_mode("ring_attention", ag.mode)
    modes["ag_matmul_2level"] = "two_level"
    modes["matmul_rs_2level"] = "two_level"
    # wire picks land as per-op entries (not the global default): the
    # analytic model only saw the AG/RS regimes, so only those ops get a
    # low-precision wire — everything else stays f32 under the default
    wires = {op: ch.wire
             for op, ch in (("ag_matmul", ag), ("matmul_rs", rs))
             if ch.wire != "f32"}
    # placement pick: the causal critical-path fraction is dimension-
    # independent (zigzag halves it at any world >= 2, and non-causal
    # placements are FLOP-identical — see analytic_ring_attention), so
    # ring attention always gets the balanced owner map. The policy
    # clamps it off ops that never declared placements.
    placements = {"ring_attention": "zigzag"}
    return OverlapPolicy(
        mode=ag.mode,
        # the latency-bound ops are kernel-capable too, so the backend
        # recommendation enumerates the full per-op mode map
        backend=recommend_backend(modes),
        modes=modes,
        ag_chunks=ag.chunks_per_rank,
        rs_chunks=rs.chunks_per_rank,
        wires=tuple(sorted(wires.items())),
        placements=tuple(sorted(placements.items())),
    )


# ---------------------------------------------------------------------------
# Empirical whole-step tuner (paper's protocol)
# ---------------------------------------------------------------------------


@dataclass
class TuneResult:
    config: object
    seconds: float
    all_timings: dict
    # repr(config) -> repro.obs.metrics.Summary (tune(record_stalls=True)):
    # the measured stall breakdown behind each candidate's timing
    stalls: dict = field(default_factory=dict)


def default_reset() -> Optional[Callable[[], None]]:
    """The platform's between-candidates signal reset.

    On hosts without real TPU remote DMA, ``backend="kernel"``
    candidates run on the emulated shmem backend, whose symmetric heaps
    and counting signal slots survive an aborted/partial timed run —
    stale state then skews (or deadlocks) the NEXT candidate's wait
    accounting. ``shmem.emulated.reset`` drops that state. On real TPU
    there is no host-side heap to clear; the caller supplies a
    device-appropriate reset (or None).
    """
    if jax.default_backend() == "tpu":
        return None
    from ..shmem import emulated

    return emulated.reset


def tune(
    make_step: Callable[[object], Callable[[], object]],
    configs: Iterable[object],
    *,
    reset="auto",
    warmup: int = 1,
    iters: int = 3,
    record_stalls: bool = False,
) -> TuneResult:
    """Time whole wrapped step functions, one config at a time.

    ``make_step(config)`` returns a zero-arg callable executing the full
    overlapped step (comm + compute + host logic). Between candidate
    configs ``reset()`` restores signal state — the paper's requirement
    that overlapped kernels cannot be replayed without resetting signals.
    The default ``reset="auto"`` resolves via :func:`default_reset`: on
    CPU hosts it is ``repro.shmem.emulated.reset``, clearing the
    symmetric heaps and signal slots a kernel-backend candidate leaves
    behind, so stale signal-slot state can never leak across timed
    candidates. Pass an explicit callable to override, or ``None`` to
    disable.

    ``record_stalls=True`` enables :mod:`repro.obs` tracing around each
    candidate (BEFORE its first compile, so compute spans are traced)
    and reduces the timed iterations' events into a per-candidate
    :class:`repro.obs.metrics.Summary` in ``TuneResult.stalls`` — the
    measured exposed-comm / overlap-efficiency breakdown behind each
    timing. Note: tracing adds host-callback overhead, so absolute
    ``seconds`` shift; the RELATIVE stall structure is the signal.
    """
    if reset == "auto":
        reset = default_reset()
    obs = None
    if record_stalls:
        from .. import obs as _obs

        obs = _obs
        was_enabled = obs.enabled()
        obs.enable()
    timings: dict = {}
    stalls: dict = {}
    best_cfg, best_t = None, float("inf")
    try:
        for cfg in configs:
            step = make_step(cfg)
            for _ in range(warmup):
                out = step()
                jax.block_until_ready(out)
                if obs is not None:
                    obs.clear()  # timed iterations only
                if reset is not None:
                    reset()
            acc = 0.0
            cfg_events = []
            for _ in range(iters):
                t0 = time.perf_counter()
                out = step()
                jax.block_until_ready(out)
                acc += time.perf_counter() - t0
                if obs is not None:
                    # drain BEFORE reset: reset drops worlds + traces
                    cfg_events.extend(obs.events(clear=True))
                if reset is not None:
                    reset()
            t = acc / iters
            timings[repr(cfg)] = t
            if obs is not None and cfg_events:
                stalls[repr(cfg)] = obs.metrics.summarize(
                    cfg_events, config=repr(cfg))
            if t < best_t:
                best_cfg, best_t = cfg, t
    finally:
        if obs is not None and not was_enabled:
            obs.disable()
    return TuneResult(best_cfg, best_t, timings, stalls)


# ---------------------------------------------------------------------------
# Chunk-centric per-layer-shape search (Syncopate-style): enumerate
# mode x backend x chunks x wire per layer shape, cache per
# (op, shape, world, hw), emit shape-keyed OverlapPolicy rules.
# ---------------------------------------------------------------------------

# (op, shape_key, world, hw_name) -> {"best": overrides, "timings": {...}}
_SEARCH_CACHE: Dict[tuple, dict] = {}

# Count of individual timed step executions performed by search() — the
# test hook pinning the cache contract: a second search with identical
# keys must leave this counter unchanged.
SEARCH_TIMINGS = 0


def clear_search_cache() -> None:
    _SEARCH_CACHE.clear()


def search_cache_key(op: str, shape, world: int, hw_spec=None) -> tuple:
    from ..ops.policy import shape_key

    hw_name = getattr(hw_spec, "name", None) if hw_spec is not None \
        else jax.default_backend()
    return (op, shape_key(shape), int(world), hw_name)


def search_candidates(op: str, chunks: Sequence[int] = (1, 2, 4)):
    """The deduplicated (mode, backend, chunks, wire) grid for ``op``,
    straight from the live registry (baseline included) — declaring a
    transport / kernel protocol / wire dtype automatically enrolls it."""
    seen, grid = set(), []
    for mode in overlap.transports_for(op, include_baseline=True):
        for backend in overlap.backends_for(op):
            if overlap.resolve_backend(op, backend, mode) != backend:
                continue  # (mode, backend) pair the registry would clamp away
            for wire in overlap.wires_for(op):
                if overlap.resolve_wire(op, wire, mode) != wire:
                    continue
                for sub in chunks:
                    n = 1 if mode in ("none", "xla", "one_shot") else int(sub)
                    cand = (mode, backend, n, wire)
                    if cand not in seen:
                        seen.add(cand)
                        grid.append(cand)
    return grid


def search(
    make_step: Callable[[tuple, object], Callable[[], object]],
    op: str,
    shapes: Sequence,
    *,
    world: int,
    hw_spec: Optional[hw.HardwareSpec] = None,
    chunks: Sequence[int] = (1, 2, 4),
    base=None,
    reset="auto",
    warmup: int = 1,
    iters: int = 2,
):
    """Search the chunk-centric schedule space PER LAYER SHAPE and
    return a shape-keyed :class:`repro.ops.OverlapPolicy`.

    For each layer shape in ``shapes`` (e.g. the QKV projection, the MLP
    matmul and the MoE dispatch of one block, as flat GEMM-dim tuples or
    per-operand shape tuples — both canonicalize through
    ``ops.shape_key``), the full registry grid
    mode x backend x chunks x wire (:func:`search_candidates`) is timed
    through the whole-step protocol of :func:`tune` —
    ``make_step(shape, resolved)`` must return the zero-arg step to
    time, with ``resolved`` a :class:`repro.ops.ResolvedOverlap`.

    Results are cached per ``(op, shape, world, hw)`` in the module
    cache: a second search with identical keys performs ZERO new
    timings (``SEARCH_TIMINGS`` is the test-pinned counter), and
    :func:`save_search_cache` / :func:`load_search_cache` round-trip the
    cache through JSON so searched policies can be committed.

    The returned policy is ``base`` (default: a fresh policy) with one
    ``with_layer`` rule per searched shape; call sites that thread
    shapes through ``policy.resolve(op, shape=...)`` — every
    ``ops.<name>(...)`` call does — then lower each site by its own
    searched schedule.
    """
    global SEARCH_TIMINGS
    from ..ops.policy import OverlapPolicy, ResolvedOverlap

    if reset == "auto":
        reset = default_reset()
    policy = base if base is not None else OverlapPolicy()
    for shape in shapes:
        key = search_cache_key(op, shape, world, hw_spec)
        entry = _SEARCH_CACHE.get(key)
        if entry is None:
            timings: Dict[str, float] = {}
            best, best_t = None, float("inf")
            for mode, backend, sub, wire in search_candidates(op, chunks):
                # the placement axis multiplies the grid only for ops
                # that declared non-contiguous placements (registry
                # clamp), so ag/rs grids — and their cache entries and
                # timing counts — are unchanged
                for placement in overlap.placements_for(op):
                    if overlap.resolve_placement(op, placement) != placement:
                        continue
                    resolved = ResolvedOverlap(mode, backend, sub, wire,
                                               placement)
                    step = make_step(shape, resolved)
                    for _ in range(warmup):
                        jax.block_until_ready(step())
                        if reset is not None:
                            reset()
                    acc = 0.0
                    for _ in range(iters):
                        t0 = time.perf_counter()
                        jax.block_until_ready(step())
                        acc += time.perf_counter() - t0
                        SEARCH_TIMINGS += 1
                        if reset is not None:
                            reset()
                    t = acc / iters
                    tag = f"{mode}/{backend}/x{sub}/{wire}"
                    if placement != "contiguous":
                        tag += f"/{placement}"
                    timings[tag] = t
                    if t < best_t:
                        best, best_t = resolved, t
            entry = {
                "best": {"mode": best.mode, "backend": best.backend,
                         "chunks": best.chunks, "wire": best.wire},
                "timings": timings,
            }
            if best.placement != "contiguous":
                entry["best"]["placement"] = best.placement
            _SEARCH_CACHE[key] = entry
        policy = policy.with_layer(op, shape, **entry["best"])
    return policy


def save_search_cache(path) -> None:
    """Commit the search cache as JSON (see :func:`load_search_cache`)."""
    import json

    entries = [
        {"op": op, "shape": list(shp), "world": world, "hw": hw_name,
         "best": entry["best"], "timings": entry["timings"]}
        for (op, shp, world, hw_name), entry in sorted(_SEARCH_CACHE.items())
    ]
    with open(path, "w") as f:
        json.dump(entries, f, indent=2, sort_keys=True)


def load_search_cache(path) -> int:
    """Load committed search results; returns the number of entries.
    Subsequent :func:`search` calls with matching keys perform zero new
    timings."""
    import json

    with open(path) as f:
        entries = json.load(f)
    for e in entries:
        key = (e["op"], tuple(e["shape"]), int(e["world"]), e["hw"])
        _SEARCH_CACHE[key] = {"best": dict(e["best"]),
                              "timings": dict(e.get("timings", {}))}
    return len(entries)
