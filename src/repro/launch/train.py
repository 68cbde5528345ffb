"""End-to-end training driver with fault tolerance.

Runs on any mesh (CPU smoke: --dp 2 --tp 2 with 4 virtual devices via
XLA_FLAGS, or the production pod). Features exercised here:
  - deterministic restart-reproducible data pipeline
  - checkpoint/restart (atomic, async, GC) + NaN-skip straggler guard
  - the overlapped train step (AG+GEMM / GEMM+RS everywhere)

Usage (CPU smoke):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  python -m repro.launch.train --arch granite-3-2b --reduced --dp 2 --tp 2 \
      --steps 20 --batch 4 --seq 64
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCHS, get_config, reduced
from ..configs.base import ParallelConfig, ShapeConfig, TrainConfig
from ..data.pipeline import SyntheticTokens
from ..train import optimizer as opt_mod
from ..train.checkpoint import Checkpointer
from .cache import enable_compile_cache
from .mesh import make_mesh
from .steps import build_train_step, batch_spec


def run(args):
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    pcfg = ParallelConfig(
        dp=args.dp, tp=args.tp, pods=args.pods,
        fsdp=not args.no_fsdp,
        overlap_mode=args.overlap,
        remat=args.remat,
        param_dtype=args.dtype, compute_dtype=args.dtype,
    )
    tcfg = TrainConfig(
        total_steps=args.steps, warmup_steps=max(1, args.steps // 10),
        learning_rate=args.lr, checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every,
    )
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch, kind="train")
    mesh = make_mesh(args.dp, args.tp, args.pods)
    built = build_train_step(cfg, pcfg, shape, mesh, tcfg)
    model = built.model

    key = jax.random.PRNGKey(tcfg.seed)
    params, _ = model.init(key, jnp.dtype(pcfg.param_dtype))
    opt_state = opt_mod.init_opt_state(
        params, jnp.dtype(pcfg.moment_dtype), kind=tcfg.optimizer
    )

    ckpt = Checkpointer(tcfg.checkpoint_dir, keep=3)
    start_step = 0
    latest = ckpt.latest_step()
    if latest is not None and not args.fresh:
        state = ckpt.restore(latest, {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start_step = latest
        print(f"[restore] resumed from step {latest}")

    data = SyntheticTokens(
        vocab_size=cfg.vocab_size, seq_len=shape.seq_len,
        global_batch=shape.global_batch, seed=tcfg.seed, mesh=mesh,
        batch_sharding=batch_spec(shape.global_batch, pcfg),
    )

    losses = []
    t0 = time.time()
    skipped = 0
    for step, (tokens, labels) in (
        (s, data.batch_at(s)) for s in range(start_step, args.steps)
    ):
        params, opt_state, _, metrics = built.fn(
            params, opt_state, tokens, labels, None
        )
        loss = float(metrics.loss)
        if not np.isfinite(loss):
            # fault/straggler guard: the compiled step already froze
            # params + optimizer state in-graph (donation-safe); just log
            skipped += 1
            print(f"step {step}: non-finite loss, update skipped in-graph")
            continue
        losses.append(loss)
        if step % args.log_every == 0:
            dt = time.time() - t0
            print(
                f"step {step:5d} loss={loss:.4f} gnorm={float(metrics.grad_norm):.3f} "
                f"lr={float(metrics.lr):.2e} ({dt:.1f}s)"
            )
        if tcfg.checkpoint_every and step > 0 and step % tcfg.checkpoint_every == 0:
            ckpt.save(step, {"params": params, "opt": opt_state})
    ckpt.save(args.steps, {"params": params, "opt": opt_state}, blocking=True)
    if losses:
        print(
            f"done: {len(losses)} steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
            f"{skipped} skipped, {(time.time()-t0):.1f}s"
        )
    else:
        print("done: nothing to do (already past target step)")
    return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default="granite-3-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--overlap", default="ring",
                    choices=["ring", "bidir", "one_shot", "none", "auto"],
                    help="overlap transport; 'auto' asks the analytic "
                         "tuner for a whole OverlapPolicy")
    ap.add_argument("--remat", default="block", choices=["none", "dots", "block"])
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--fresh", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
