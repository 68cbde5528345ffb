"""Serving driver: paged KV cache + chunked prefill + continuous batching.

Builds BOTH serving programs from one model:
  * the chunked-prefill program (``build_prefill_chunk_step``) — C-token
    prompt chunks written straight into the paged KV pools, one request
    stream per data shard;
  * the paged decode step (``build_paged_decode_step``) — one token per
    slot at per-slot positions through the block tables;
and drives them with :class:`repro.serve.PagedEngine` under a seeded
synthetic load stream (``repro.serve.load``). ``--tokenwise`` instead
runs the legacy dense-cache engine (prompt ingestion token-by-token
through the decode program) for comparison.

Prefill and decode may carry SEPARATE overlap policies: prefill is
throughput-bound (ag_matmul/matmul_rs in the chunk projections), decode
latency-bound (flash_decode/a2a_ep) — pass ``--prefill-overlap`` to
split them.

CPU smoke:
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python -m repro.launch.serve --arch granite-3-2b --reduced --dp 2 --tp 2
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import ARCHS, get_config, reduced
from ..configs.base import ParallelConfig, ShapeConfig
from ..ops.policy import OverlapPolicy
from ..serve import (
    Engine,
    LoadSpec,
    PagedEngine,
    PagedKVCache,
    ServeConfig,
    drive,
    generate,
)
from .cache import enable_compile_cache
from .mesh import make_mesh
from .steps import (
    build_decode_step,
    build_paged_decode_step,
    build_prefill_chunk_cp_step,
    build_prefill_chunk_step,
    data_world,
)


def _with_policy(pcfg: ParallelConfig, policy) -> ParallelConfig:
    """A copy of ``pcfg`` carrying ``policy`` as its overlap policy
    (legacy overlap fields reset so the config conflict check is quiet)."""
    defaults = {
        f.name: f.default
        for f in dataclasses.fields(ParallelConfig)
        if f.name in ParallelConfig._LEGACY_OVERLAP_FIELDS
    }
    return dataclasses.replace(pcfg, overlap=policy, **defaults)


def _shardings(mesh, pspecs):
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def build_paged_engine(
    cfg, pcfg: ParallelConfig, scfg: ServeConfig, mesh, *,
    cache_dtype=None, prefill_policy=None, seed: int = 0, eos_id: int = -1,
    prefill_cp: bool = False, cp_placement: str = "zigzag",
    cp_attend: str = "ring",
) -> PagedEngine:
    """Compile the two serving programs and wire up the paged engine.

    ``prefill_policy`` (an OverlapPolicy) gives the chunked-prefill
    program its own overlap resolution; decode keeps ``pcfg``'s.

    ``prefill_cp`` switches prefill to the CONTEXT-PARALLEL program:
    one stream whose chunk shards over the data axis by the balanced
    ``cp_placement`` map, chunk-internal attention through the
    placement-aware ring_attention op (``cp_attend="ring"``; ``"dense"``
    is the bit-exact-vs-dense-path variant). The engine then plans at
    most one prefill chunk per step (the whole mesh cooperates on it)
    while decode keeps its data-parallel slot sharding."""
    cache_dtype = cache_dtype or jnp.dtype(pcfg.compute_dtype)
    assert scfg.chunk % pcfg.tp == 0, "prefill chunk must split over tp"
    dw = data_world(pcfg)
    dp_shards = 1 if prefill_cp else (dw if scfg.batch >= dw else 1)
    # probe the allocator for the derived pool geometry
    kv = PagedKVCache(batch=scfg.batch, max_len=scfg.max_len,
                      page_size=scfg.page_size, num_pages=scfg.num_pages,
                      dp_shards=dp_shards)
    scfg = dataclasses.replace(scfg, num_pages=kv.num_pages)
    shape = ShapeConfig("serve", seq_len=scfg.max_len,
                        global_batch=scfg.batch, kind="decode")
    dec = build_paged_decode_step(
        cfg, pcfg, shape, mesh, num_pages=kv.num_pages,
        page_size=scfg.page_size, pages_per_slot=kv.pages_per_slot,
        cache_dtype=cache_dtype)
    pre_pcfg = (_with_policy(pcfg, prefill_policy)
                if prefill_policy is not None else pcfg)
    if prefill_cp:
        assert scfg.chunk % (data_world(pcfg) * pcfg.tp) == 0, \
            "cp prefill chunk must split over dp*tp"
        pre = build_prefill_chunk_cp_step(
            cfg, pre_pcfg, mesh, chunk=scfg.chunk,
            num_pages=kv.num_pages, page_size=scfg.page_size,
            pages_per_slot=kv.pages_per_slot, cache_dtype=cache_dtype,
            placement=cp_placement, cp_attend=cp_attend)
    else:
        pre = build_prefill_chunk_step(
            cfg, pre_pcfg, mesh, chunk=scfg.chunk, n_streams=dp_shards,
            num_pages=kv.num_pages, page_size=scfg.page_size,
            pages_per_slot=kv.pages_per_slot, cache_dtype=cache_dtype)
    # params and pools live on the mesh as the programs take them, so a
    # step moves no weights between devices
    params, pspecs = dec.model.init(jax.random.PRNGKey(seed),
                                    jnp.dtype(pcfg.param_dtype))
    params = jax.device_put(params, _shardings(mesh, pspecs))
    pools = jax.tree.map(
        lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
        dec.in_shapes[1], _shardings(mesh, dec.in_pspecs[1]))
    return PagedEngine(pre.fn, dec.fn, params, pools, scfg,
                       dp_shards=dp_shards, eos_id=eos_id, seed=seed,
                       pcfg=pcfg, prefill_pcfg=pre_pcfg,
                       prefill_cp=prefill_cp, cp_placement=cp_placement)


def build_tokenwise_engine(
    cfg, pcfg: ParallelConfig, batch: int, max_len: int, mesh, *,
    cache_dtype=None, seed: int = 0, eos_id: int = -1,
) -> Engine:
    """The legacy path: dense per-slot KV caches, prompt ingestion
    token-by-token through the decode program."""
    cache_dtype = cache_dtype or jnp.dtype(pcfg.compute_dtype)
    shape = ShapeConfig("serve", seq_len=max_len, global_batch=batch,
                        kind="decode")
    built = build_decode_step(cfg, pcfg, shape, mesh, cache_dtype=cache_dtype)
    params, _ = built.model.init(jax.random.PRNGKey(seed),
                                 jnp.dtype(pcfg.param_dtype))
    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          built.in_shapes[1])
    return Engine(built.fn, params, caches, batch=batch, max_len=max_len,
                  eos_id=eos_id, seed=seed, pcfg=pcfg)


def run(args):
    trace_path = getattr(args, "trace", None)
    if trace_path:
        # enable BEFORE the engines compile so compute spans are traced
        from .. import obs

        obs.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    pcfg = ParallelConfig(
        dp=args.dp, tp=args.tp, fsdp=not args.no_fsdp,
        param_dtype=args.dtype, compute_dtype=args.dtype,
        overlap=OverlapPolicy(mode=getattr(args, "overlap", "none")),
    )
    mesh = make_mesh(args.dp, args.tp)
    tokenwise = getattr(args, "tokenwise", False)
    if tokenwise:
        eng = build_tokenwise_engine(cfg, pcfg, args.batch, args.max_len, mesh)
    else:
        prefill_policy = None
        if getattr(args, "prefill_overlap", None):
            prefill_policy = OverlapPolicy(mode=args.prefill_overlap)
        scfg = ServeConfig(
            batch=args.batch, max_len=args.max_len,
            page_size=getattr(args, "page_size", 16),
            num_pages=getattr(args, "num_pages", 0),
            chunk=getattr(args, "chunk", 16),
            token_budget=getattr(args, "token_budget", 64),
        )
        eng = build_paged_engine(
            cfg, pcfg, scfg, mesh, prefill_policy=prefill_policy,
            prefill_cp=getattr(args, "prefill_cp", False),
            cp_placement=getattr(args, "cp_placement", "zigzag"),
            cp_attend=getattr(args, "cp_attend", "ring"))
    print("engine:", "tokenwise" if tokenwise else "paged")
    print("overlap modes:", eng.overlap_modes())
    spec = LoadSpec(
        n_requests=args.requests,
        rate_rps=getattr(args, "rate", 32.0),
        prompt_lens=(getattr(args, "prompt_min", 4),
                     getattr(args, "prompt_max", 8)),
        max_new_tokens=args.new_tokens,
        temperature=args.temperature,
        seed=getattr(args, "seed", 0),
    )
    arrivals = generate(spec, cfg.vocab_size)
    t0 = time.time()
    leftover = drive(eng, arrivals,
                     max_steps=getattr(args, "max_steps", 100_000),
                     time_scale=getattr(args, "time_scale", 0.0))
    dt = time.time() - t0
    m = eng.metrics()
    print(f"served {args.requests - len(leftover)}/{args.requests} requests "
          f"in {dt:.1f}s ({m.steps} steps: {m.steps_prefill} prefill + "
          f"{m.steps_decode} decode)")
    print(m)
    if trace_path:
        from .. import obs

        ev = obs.events(clear=True)
        n = obs.trace.save(trace_path, ev)
        print(f"wrote {n} trace events to {trace_path}")
        if ev:
            print(obs.metrics.summarize(ev))
    return eng


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--tokenwise", action="store_true",
                    help="legacy dense-cache engine (token-by-token prefill)")
    ap.add_argument("--overlap", default="none",
                    help="decode-phase overlap mode")
    ap.add_argument("--prefill-overlap", default=None,
                    help="separate overlap mode for the chunked-prefill program")
    ap.add_argument("--prefill-cp", action="store_true",
                    help="context-parallel chunked prefill: shard each "
                         "chunk over the data axis through the balanced "
                         "ring-attention op (one stream, whole-mesh)")
    ap.add_argument("--cp-placement", default="zigzag",
                    choices=("contiguous", "zigzag", "striped"),
                    help="chunk-row -> data-rank owner map for --prefill-cp")
    ap.add_argument("--cp-attend", default="ring", choices=("ring", "dense"),
                    help="--prefill-cp chunk attention: ring (balanced "
                         "ring_attention + prefix merge) or dense "
                         "(gathered pages; bit-exact vs the dense path)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=0,
                    help="pool pages per DP shard (0 = dense-equivalent)")
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk length (multiple of tp)")
    ap.add_argument("--token-budget", type=int, default=64)
    ap.add_argument("--rate", type=float, default=32.0,
                    help="Poisson arrival rate (requests/s)")
    ap.add_argument("--prompt-min", type=int, default=4)
    ap.add_argument("--prompt-max", type=int, default=8)
    ap.add_argument("--time-scale", type=float, default=0.0,
                    help="arrival-time multiplier (0 = release all up front)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", nargs="?", const="serve_trace.json",
                    default=None, metavar="PATH",
                    help="enable repro.obs tracing and write the run's "
                         "Chrome-trace JSON (kernel-backend runs record "
                         "per-PE engine events; graph runs span-label only)")
    args = ap.parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
