#!/usr/bin/env python3
"""Smoke check on the chip: serve granite-3-2b at its published widths.

    python3 chip_smoke.py             # one TPU
    python3 chip_smoke.py --chips 4   # four TPUs of one host

One chip: granite-3-2b at full width and depth (40 layers, d_model 2048,
32q/8kv x 64, d_ff 8192, vocab 49155) in bf16 with seeded random
weights, served through the normal builders (``build_paged_engine`` ->
``PagedEngine`` -> ``serve.drive``) on a (1, 1) mesh: batch 8, max_len
2048, page size 16, prefill chunk 256, eight seeded requests with
128-1024-token prompts and 32 greedy new tokens each. It fails if a
request is unserved or truncated, if a logit is non-finite, or if the
compiled decode step holds no Pallas kernel (``tpu_custom_call``).

Four chips (only these two checks): the same requests served at dp=1
tp=4, compared with the tp=1 program on device 0 (prefill logits within
a bf16 tolerance; the greedy-token match rate is printed); and
``ops.ag_matmul`` / ``ops.matmul_rs`` in ring mode on the shmem kernel
backend against the XLA-collective graph backend at granite's tp=4
projection widths.

Run from the root of a checkout. It exits non-zero and prints no result
when JAX finds no TPU or when the checkout's ``src/repro`` is missing.
The lines before the last are facts of the run (device, compile
seconds, counts, peak device memory); the last line is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit(f"chip_smoke.py: no src/repro beside {__file__}; "
             "run it from the root of a checkout")
sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import ops  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ParallelConfig  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_paged_engine  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.serve import LoadSpec, ServeConfig, drive, generate  # noqa: E402

ARCH = "granite-3-2b"
SEED = 0  # weights, prompts and the kernel checks' operands
# tp=4 vs tp=1 prefill logits: max |difference| over max |logit|. The two
# programs round bf16 partial sums at different places (four per-rank
# partials psum'ed vs one full product) in each of 40 layers; 8 bits of
# mantissa give ~4e-3 per rounding, and the residual stream compounds them.
TP_LOGIT_TOL = 5e-2
# kernel vs graph: the same bf16 products; the RS kernel pushes each
# partial rounded to bf16 before the f32 reduction, so a few bf16 ulps
TP_KERNEL_TOL = 2e-2
TP4_WIDTHS = (  # granite-3-2b at tp=4: 512 rows per rank, d_model 2048
    ("ag_matmul", "qkv", 768),        # 2048 -> (32 + 2*8) * 64 / 4
    ("ag_matmul", "mlp_in", 4096),    # 2048 -> 2 * 8192 / 4 (gated)
    ("matmul_rs", "attn_out", 512),   # 32 * 64 / 4 -> 2048
    ("matmul_rs", "mlp_out", 2048),   # 8192 / 4 -> 2048
)


def say(*parts) -> None:
    print(*parts, flush=True)


def _compiled(fn, args, name: str, facts: dict, check_rows=None):
    """Compile ``fn`` for ``args`` ahead of time (timed) and return a
    drop-in callable that records whether every logit it returns is
    finite (``check_rows(args)`` picks the rows that carry requests)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    facts[f"compile_s_{name}"] = time.perf_counter() - t0
    facts[f"hlo_{name}"] = compiled.as_text()
    facts.setdefault("logits", [])
    facts.setdefault("finite", True)

    def call(*a):
        logits, pools = compiled(*a)
        rows = np.asarray(logits, np.float32)
        if check_rows is not None:
            rows = rows[check_rows(a)]
        facts["finite"] &= bool(np.isfinite(rows).all())
        if name == "prefill":
            facts["logits"].append(rows)
        return logits, pools

    return call


def serve(cfg, pcfg: ParallelConfig, mesh, *, n_requests: int = 8,
          prompt_lens=(128, 1024), new_tokens: int = 32, batch: int = 8,
          max_len: int = 2048, page_size: int = 16, chunk: int = 256,
          seed: int = 0, weights=None):
    """Serve ``n_requests`` seeded greedy requests through the paged
    engine on ``mesh``; both programs are compiled ahead of time so
    their compile seconds and HLO are facts of the run. ``weights``
    (host copies of tp=1 params) replace the engine's seeded ones.
    Returns the facts and the engine."""
    # one full chunk plus every decode slot fits each step's budget
    scfg = ServeConfig(batch=batch, max_len=max_len, page_size=page_size,
                       chunk=chunk, token_budget=chunk + batch)
    t0 = time.perf_counter()
    eng = build_paged_engine(cfg, pcfg, scfg, mesh, seed=seed)
    if weights is not None:
        eng.params = jax.device_put(
            tp_relayout(weights, cfg, pcfg),
            jax.tree.map(lambda x: x.sharding, eng.params))
    jax.block_until_ready((eng.params, eng.pools))
    facts = {"build_s": time.perf_counter() - t0,
             "peak_after_build": _peak_bytes()}
    kv, n = eng.kv, eng.dp_shards
    i32 = np.int32
    dec_args = (eng.params, eng.pools, jnp.asarray(kv.table),
                jnp.asarray(kv.lens), jnp.asarray(np.zeros(batch, bool)),
                jnp.asarray(np.zeros((batch, 1), i32)))
    pre_args = (eng.params, eng.pools,
                jnp.asarray(np.zeros((n, kv.pages_per_slot), i32)),
                jnp.asarray(np.zeros(n, i32)), jnp.asarray(np.zeros(n, i32)),
                jnp.asarray(np.zeros((n, chunk), i32)))
    eng.decode_fn = _compiled(eng.decode_fn, dec_args, "decode", facts,
                              check_rows=lambda a: np.asarray(a[4]))
    eng.prefill_fn = _compiled(eng.prefill_fn, pre_args, "prefill", facts,
                               check_rows=lambda a: np.asarray(a[4]) > 0)
    arrivals = generate(LoadSpec(n_requests=n_requests, prompt_lens=prompt_lens,
                                 max_new_tokens=new_tokens, temperature=0.0,
                                 seed=seed), cfg.vocab_size)
    reqs = [r for _, r in arrivals]
    t0 = time.perf_counter()
    leftover = drive(eng, arrivals, time_scale=0.0)
    facts["serve_s"] = time.perf_counter() - t0
    m = eng.metrics()
    facts.update(
        served=m.requests_completed, leftover=len(leftover),
        truncated=m.requests_truncated,
        short=sum(len(r.out_tokens) != new_tokens for r in reqs),
        steps=m.steps, steps_prefill=m.steps_prefill,
        steps_decode=m.steps_decode,
        tokens=[list(r.out_tokens) for r in reqs])
    facts["served_ok"] = (facts["served"] == n_requests and not leftover
                          and not facts["truncated"] and not facts["short"])
    return facts, eng


def tp_relayout(params, cfg, pcfg: ParallelConfig) -> dict:
    """tp=1 packed params (host arrays) in ``pcfg``'s tp-sharded packed
    layout: the same logical weights, so both programs run one model.
    Seeded init draws per-rank segments, so it cannot give them."""
    one = build_model(cfg, _pcfg(1))
    many = build_model(cfg, pcfg)
    tp = pcfg.tp
    assert pcfg.dp == 1 and many.info.kv_rep == 1, "dense tp-only layouts"

    def leaf(name, x, s1, st):
        rows = np.asarray(x)
        rows = (rows if rows.ndim == 2 else rows[None])[:, :s1.numel]
        rows = rows.reshape((len(rows),) + s1.local_shape)
        if not st.tp_sharded:
            out = rows.reshape(len(rows), -1)
        else:
            # the split dim; wkv/bkv hold [K | V] and a gated wi holds
            # [gate | up], each split over the ranks on its own
            ax = 1 + next(i for i, (a, b) in enumerate(
                zip(s1.local_shape, st.local_shape)) if a != b)
            parts = 2 if name in ("wkv", "bkv") or (
                name == "wi" and cfg.gated_mlp) else 1
            n_loc = st.local_shape[ax - 1] // parts
            blocks = np.split(rows, parts, axis=ax)
            pad = [(0, 0)] * rows.ndim
            pad[ax] = (0, tp * n_loc - blocks[0].shape[ax])  # vocab padding
            blocks = [np.pad(b, pad) for b in blocks]
            out = np.concatenate([
                np.concatenate([np.take(b, range(r * n_loc, (r + 1) * n_loc),
                                        axis=ax) for b in blocks], axis=ax)
                .reshape(len(rows), -1) for r in range(tp)], axis=1)
        return out if np.asarray(x).ndim == 2 else out[0]

    def walk(tree, specs1, specst):
        return {k: (walk(v, specs1[k], specst[k]) if isinstance(v, dict)
                    else leaf(k, v, specs1[k], specst[k]))
                for k, v in tree.items()}

    return {"top": walk(params["top"], one.top_specs, many.top_specs),
            "layers": walk(params["layers"], one.layer_specs,
                           many.layer_specs)}


def _device() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _peak_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def _pcfg(tp: int) -> ParallelConfig:
    return ParallelConfig(dp=1, tp=tp, param_dtype="bfloat16",
                          compute_dtype="bfloat16")


def _mesh(devices):
    return jax.sharding.Mesh(np.asarray(devices).reshape(1, len(devices)),
                             ("data", "model"))


def _report(tag: str, facts: dict) -> None:
    say(f"{tag}: compile decode {facts['compile_s_decode']:.3f} s, "
        f"prefill {facts['compile_s_prefill']:.3f} s; build "
        f"{facts['build_s']:.3f} s (peak bytes after build "
        f"{facts['peak_after_build']}); served {facts['served']} "
        f"(leftover {facts['leftover']}, truncated {facts['truncated']}, "
        f"short {facts['short']}) in {facts['steps']} steps "
        f"({facts['steps_prefill']} prefill + {facts['steps_decode']} "
        f"decode), {facts['serve_s']:.3f} s; logits finite {facts['finite']}")


def one_chip(cfg, seed: int, **serve_kw) -> bool:
    facts, _ = serve(cfg, _pcfg(1), _mesh(jax.devices()[:1]), seed=seed,
                     **serve_kw)
    _report("serve tp=1", facts)
    kernel = "tpu_custom_call" in facts["hlo_decode"]
    say(f"decode step holds a Pallas kernel (tpu_custom_call): {kernel}")
    say(f"peak_bytes_in_use: {_peak_bytes()}")
    return facts["served_ok"] and facts["finite"] and kernel


def _spans(tree, n: int) -> bool:
    return all(len(x.sharding.device_set) == n for x in jax.tree.leaves(tree))


def tp_compare(cfg, seed: int, **serve_kw) -> bool:
    """tp=4 serving on four chips vs the tp=1 program on device 0."""
    ref, eng = serve(cfg, _pcfg(1), _mesh(jax.devices()[:1]), seed=seed,
                     **serve_kw)
    _report("serve tp=1 (device 0)", ref)
    weights = jax.device_get(eng.params)
    del eng
    gc.collect()
    got, eng = serve(cfg, _pcfg(4), _mesh(jax.devices()[:4]), seed=seed,
                     weights=weights, **serve_kw)
    _report("serve tp=4", got)
    # the weights and pools live on all four chips, each holding its
    # quarter of every tp-sharded weight
    wq = eng.params["layers"]["attn"]["wq"]
    shard_cols = wq.addressable_shards[0].data.shape[-1]
    spans = (_spans(eng.params, 4) and _spans(eng.pools, 4)
             and shard_cols * 4 == wq.shape[-1])
    say(f"tp=4 params and pools span 4 devices: {spans} "
        f"(wq {wq.shape} -> shard {wq.addressable_shards[0].data.shape})")
    a, b = np.concatenate(got["logits"]), np.concatenate(ref["logits"])
    err = float(np.abs(a - b).max() / np.abs(b).max())
    say(f"prefill logits tp=4 vs tp=1 ({len(a)} calls): max|diff|/max|ref| "
        f"= {err} (tol {TP_LOGIT_TOL})")
    pairs = [(x, y) for r1, r4 in zip(ref["tokens"], got["tokens"])
             for x, y in zip(r1, r4)]
    match = sum(x == y for x, y in pairs) / max(1, len(pairs))
    first = sum(r1[:1] == r4[:1] for r1, r4 in zip(ref["tokens"], got["tokens"]))
    say(f"greedy tokens tp=4 vs tp=1: match rate {match} over {len(pairs)}; "
        f"first tokens equal {first}/{len(ref['tokens'])}")
    say(f"peak_bytes_in_use (device 0): {_peak_bytes()}")
    return (ref["served_ok"] and got["served_ok"] and ref["finite"]
            and got["finite"] and spans and err <= TP_LOGIT_TOL)


def kernel_vs_graph(seed: int, rows_per_rank: int = 512,
                    d: int = 2048, widths=TP4_WIDTHS) -> bool:
    """The shmem protocols on real remote DMA vs XLA's collectives."""
    w = 4
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:w]), ("tp",))
    rows = rows_per_rank * w
    rng = np.random.RandomState(seed)
    ok = True
    for op, name, width in widths:
        if op == "ag_matmul":
            x_shape, w_shape = (rows, d), (d, width * w)
            specs, out = (P("tp", None), P(None, "tp")), P(None, "tp")
        else:
            x_shape, w_shape = (rows, width * w), (width * w, d)
            specs, out = (P(None, "tp"), P("tp", None)), P("tp", None)
        x = jax.device_put(
            jnp.asarray(rng.randn(*x_shape), jnp.bfloat16),
            NamedSharding(mesh, specs[0]))
        wt = jax.device_put(
            jnp.asarray(rng.randn(*w_shape) / np.sqrt(w_shape[0]),
                        jnp.bfloat16), NamedSharding(mesh, specs[1]))
        res = {}
        for backend in ("graph", "kernel"):
            f = jax.jit(jax.shard_map(
                lambda a, b, backend=backend: getattr(ops, op)(
                    a, b, axis="tp", mode="ring", backend=backend,
                    out_dtype=jnp.bfloat16),
                mesh=mesh, in_specs=specs, out_specs=out, check_vma=False))
            t0 = time.perf_counter()
            c = f.lower(x, wt).compile()
            secs = time.perf_counter() - t0
            res[backend] = np.asarray(c(x, wt), np.float32)
            if backend == "kernel":
                say(f"{op} {name}: kernel compile {secs:.3f} s, "
                    f"tpu_custom_call {'tpu_custom_call' in c.as_text()}")
        err = float(np.abs(res["kernel"] - res["graph"]).max()
                    / np.abs(res["graph"]).max())
        passed = err <= TP_KERNEL_TOL and bool(np.isfinite(res["kernel"]).all())
        say(f"{op} {name} ({x_shape} x {w_shape}) kernel vs graph: "
            f"max|diff|/max|graph| = {err} (tol {TP_KERNEL_TOL}) "
            f"{'pass' if passed else 'FAIL'}")
        ok &= passed
    return ok


@contextlib.contextmanager
def _watchdog(seconds: float):
    """A hung remote-DMA protocol would hold the chip until the caller's
    time limit; end the process first, with a failing exit code."""
    def fire():
        say(f"chip_smoke.py: phase not done after {seconds} s; giving up")
        os._exit(1)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    try:
        yield
    finally:
        t.cancel()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    device = _device()
    say(f"device: {device}")
    if device["platform"] != "tpu":
        say("chip_smoke.py: JAX finds no TPU; nothing was run")
        return 1
    if device["count"] < args.chips:
        say(f"chip_smoke.py: --chips {args.chips} needs {args.chips} TPUs")
        return 1
    say(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    if args.chips == 1:
        with _watchdog(1100):
            ok = one_chip(cfg, SEED)
    else:
        with _watchdog(900):
            ok = tp_compare(cfg, SEED)
        with _watchdog(600):
            ok = kernel_vs_graph(SEED) and ok
    if not ok:
        say("chip_smoke.py: FAILED")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
