"""Step builders: wrap the shard_map-local model functions into jitted
SPMD programs with the correct input/output shardings for a given
(arch config, parallel config, shape cell, mesh).

This is the single place where logical batch placement is decided:
  batch dim -> ("pod", "data") when global_batch >= dp*pods, replicated
  otherwise (e.g. long_500k with global_batch=1).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig, ParallelConfig, ShapeConfig, TrainConfig
from ..models import build_model
from ..train import optimizer as opt_mod
from ..train.train_step import make_train_step


def batch_axes(pcfg: ParallelConfig):
    return ("pod", "data") if pcfg.pods > 1 else ("data",)


def data_world(pcfg: ParallelConfig) -> int:
    return pcfg.dp * pcfg.pods


def batch_spec(global_batch: int, pcfg: ParallelConfig, extra_dims: int = 1) -> P:
    if global_batch >= data_world(pcfg):
        return P(batch_axes(pcfg), *([None] * extra_dims))
    return P(*([None] * (extra_dims + 1)))


def local_batch(global_batch: int, pcfg: ParallelConfig) -> int:
    w = data_world(pcfg)
    return global_batch // w if global_batch >= w else global_batch


# ---------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# ---------------------------------------------------------------------------


def input_specs(
    cfg: ModelConfig, shape: ShapeConfig, pcfg: ParallelConfig, model=None
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (shapes, pspecs) for the step inputs of this cell."""
    gb, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((gb, s), jnp.int32)
    bspec = batch_spec(gb, pcfg)
    shapes: Dict[str, Any] = {}
    pspecs: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        shapes["tokens"] = tok
        pspecs["tokens"] = bspec
        if shape.kind == "train":
            shapes["labels"] = tok
            pspecs["labels"] = bspec
        if cfg.family == "vlm":
            shapes["vision"] = jax.ShapeDtypeStruct(
                (gb, cfg.vision_tokens, cfg.vision_dim), jnp.bfloat16
            )
            pspecs["vision"] = batch_spec(gb, pcfg, extra_dims=2)
        if cfg.family == "whisper":
            fp = model.frames_padded if model is not None else cfg.encoder_frames
            shapes["frames"] = jax.ShapeDtypeStruct((gb, fp, cfg.d_model), jnp.bfloat16)
            pspecs["frames"] = batch_spec(gb, pcfg, extra_dims=2)
    else:  # decode: one new token + KV caches of length seq_len
        shapes["token"] = jax.ShapeDtypeStruct((gb, 1), jnp.int32)
        pspecs["token"] = bspec
    return shapes, pspecs


def cache_specs(model, shape: ShapeConfig, pcfg: ParallelConfig, dtype=jnp.bfloat16):
    """Global cache ShapeDtypeStructs + pspecs for decode cells."""
    gb = shape.global_batch
    b_loc = local_batch(gb, pcfg)
    local = model.cache_shapes(b_loc, shape.seq_len, dtype)
    batched = gb >= data_world(pcfg)
    seq_sharded = model._kv_seq_sharded()
    baxes = batch_axes(pcfg)

    def globalize(leaf, name):
        shape_l = list(leaf.shape)
        spec = [None] * len(shape_l)
        # find batch dim: caches are (n_super, [n_sub,] B, ...) — B is the
        # dim whose size equals b_loc at index 1 or 2.
        b_idx = 1 if shape_l[1] == b_loc else 2
        if batched:
            shape_l[b_idx] = b_loc * data_world(pcfg)
            spec[b_idx] = baxes if len(baxes) > 1 else baxes[0]
        elif seq_sharded and name in ("k", "v"):
            # sequence-sharded KV over "data" (distributed flash decode)
            shape_l[-2] = leaf.shape[-2] * pcfg.dp
            spec[-2] = "data"
        return jax.ShapeDtypeStruct(tuple(shape_l), leaf.dtype), P(*spec)

    shapes, specs = {}, {}
    for k, v in local.items():
        if isinstance(v, dict):
            sub_s, sub_p = {}, {}
            for kk, vv in v.items():
                sub_s[kk], sub_p[kk] = globalize(vv, kk)
            shapes[k], specs[k] = sub_s, sub_p
        else:
            shapes[k], specs[k] = globalize(v, k)
    return shapes, specs


# ---------------------------------------------------------------------------
# Step builders
# ---------------------------------------------------------------------------


@dataclass
class BuiltStep:
    fn: Any  # jitted
    in_shapes: Tuple
    in_pspecs: Tuple
    model: Any


def _shard(mesh, fn, in_specs, out_specs, donate=()):
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False),
        donate_argnums=donate,
    )


def build_train_step(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    shape: ShapeConfig,
    mesh,
    tcfg: Optional[TrainConfig] = None,
) -> BuiltStep:
    if tcfg is None:
        tcfg = TrainConfig(
            optimizer="momentum" if cfg.param_count() > 500e9 else "adamw"
        )
    model = build_model(cfg, pcfg)
    pdt = jnp.dtype(pcfg.param_dtype)
    param_shapes, pspec = model.param_shapes(pdt)
    mdt = jnp.dtype(pcfg.moment_dtype)
    opt_shapes = opt_mod.opt_state_shapes(param_shapes, mdt, kind=tcfg.optimizer)
    # optimizer state shards exactly like params (nu is a placeholder in
    # momentum mode -> replicated)
    nu_pspec = (
        jax.tree.map(lambda _: P(), param_shapes) if tcfg.optimizer == "momentum"
        else pspec
    )
    opt_pspec = opt_mod.OptState(P(), pspec, nu_pspec)
    if cfg.family == "whisper":
        spec_tree = {"top": model.top_specs, "encoder": model.enc_specs,
                     "layers": model.dec_specs}
    else:
        spec_tree = {"top": model.top_specs, "layers": model.layer_specs}
    step_local = make_train_step(model, tcfg, pcfg, spec_tree)

    in_shapes, in_pspecs = input_specs(cfg, shape, pcfg, model)

    def fn(params, opt_state, tokens, labels, extra):
        return step_local(params, opt_state, None, tokens, labels, extra)

    extra_keys = [k for k in in_shapes if k not in ("tokens", "labels")]
    extra_shapes = {k: in_shapes[k] for k in extra_keys} if extra_keys else None
    extra_specs = {k: in_pspecs[k] for k in extra_keys} if extra_keys else None

    from ..train.train_step import TrainStepOut

    jitted = _shard(
        mesh,
        fn,
        (pspec, opt_pspec, in_pspecs["tokens"], in_pspecs["labels"], extra_specs),
        (pspec, opt_pspec, None, TrainStepOut(P(), P(), P())),
        donate=(0, 1),  # params + optimizer state update in place
    )
    all_shapes = (param_shapes, opt_shapes, in_shapes["tokens"],
                  in_shapes["labels"], extra_shapes)
    return BuiltStep(jitted, all_shapes, (pspec, opt_pspec), model)


def build_prefill_step(
    cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig, mesh
) -> BuiltStep:
    """Forward-only (inference prefill): full-sequence forward, last-token
    logits out. No optimizer, no backward."""
    model = build_model(cfg, pcfg)
    pdt = jnp.dtype(pcfg.param_dtype)
    param_shapes, pspec = model.param_shapes(pdt)
    in_shapes, in_pspecs = input_specs(cfg, shape, pcfg, model)

    def fn(params, tokens, extra):
        return model.prefill_logits_local(params, tokens, extra)

    extra_keys = [k for k in in_shapes if k != "tokens"]
    extra_shapes = {k: in_shapes[k] for k in extra_keys} if extra_keys else None
    extra_specs = {k: in_pspecs[k] for k in extra_keys} if extra_keys else None
    out_spec = batch_spec(shape.global_batch, pcfg)
    jitted = _shard(mesh, fn, (pspec, in_pspecs["tokens"], extra_specs), out_spec)
    return BuiltStep(jitted, (param_shapes, in_shapes["tokens"], extra_shapes),
                     (pspec,), model)


def build_decode_step(
    cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig, mesh,
    cache_dtype=jnp.bfloat16,
) -> BuiltStep:
    """serve_step: one new token against KV caches of length seq_len."""
    model = build_model(cfg, pcfg)
    pdt = jnp.dtype(pcfg.param_dtype)
    param_shapes, pspec = model.param_shapes(pdt)
    in_shapes, in_pspecs = input_specs(cfg, shape, pcfg, model)
    c_shapes, c_specs = cache_specs(model, shape, pcfg, cache_dtype)

    def fn(params, caches, cache_len, token):
        return model.decode_step_local(params, caches, cache_len, token)

    out_logits_spec = batch_spec(shape.global_batch, pcfg)
    jitted = _shard(
        mesh,
        fn,
        (pspec, c_specs, None, in_pspecs["token"]),
        (out_logits_spec, c_specs),
        donate=(1,),  # KV caches update in place
    )
    cache_len = jax.ShapeDtypeStruct((), jnp.int32)
    return BuiltStep(
        jitted,
        (param_shapes, c_shapes, cache_len, in_shapes["token"]),
        (pspec, c_specs),
        model,
    )


def _pool_specs(model, num_pages: int, page_size: int, dtype):
    """Paged KV pools are replicated specs (each rank holds its own
    heads-local replica, like the dense decode caches)."""
    shapes = model.paged_cache_shapes(num_pages, page_size, dtype)
    specs = jax.tree.map(lambda s: P(*([None] * len(s.shape))), shapes)
    return shapes, specs


def build_paged_decode_step(
    cfg: ModelConfig, pcfg: ParallelConfig, shape: ShapeConfig, mesh,
    *, num_pages: int, page_size: int, pages_per_slot: int,
    cache_dtype=jnp.bfloat16,
) -> BuiltStep:
    """Decode step against the paged KV pools: one new token per slot at
    per-slot positions (serve/kvcache.py block tables)."""
    model = build_model(cfg, pcfg)
    pdt = jnp.dtype(pcfg.param_dtype)
    param_shapes, pspec = model.param_shapes(pdt)
    p_shapes, p_specs = _pool_specs(model, num_pages, page_size, cache_dtype)
    gb = shape.global_batch
    vec_spec = batch_spec(gb, pcfg, extra_dims=0)

    def paged_decode_step(params, pools, table, lengths, active, token):
        return model.decode_step_paged_local(
            params, pools, table, lengths, active, token)

    jitted = _shard(
        mesh,
        paged_decode_step,
        (pspec, p_specs, batch_spec(gb, pcfg), vec_spec, vec_spec,
         batch_spec(gb, pcfg)),
        (batch_spec(gb, pcfg), p_specs),
        donate=(1,),  # pools update in place
    )
    in_shapes = (
        param_shapes,
        p_shapes,
        jax.ShapeDtypeStruct((gb, pages_per_slot), jnp.int32),
        jax.ShapeDtypeStruct((gb,), jnp.int32),
        jax.ShapeDtypeStruct((gb,), jnp.bool_),
        jax.ShapeDtypeStruct((gb, 1), jnp.int32),
    )
    return BuiltStep(jitted, in_shapes, (pspec, p_specs), model)


def build_prefill_chunk_step(
    cfg: ModelConfig, pcfg: ParallelConfig, mesh,
    *, chunk: int, n_streams: int, num_pages: int, page_size: int,
    pages_per_slot: int, cache_dtype=jnp.bfloat16,
) -> BuiltStep:
    """Chunked-prefill program: one C-token chunk of one request PER DATA
    SHARD (``n_streams`` = number of concurrent prefill streams — the
    data world when decode slots are sharded, else 1), writing K/V into
    the paged pools and returning the last-valid-token logits per
    stream. Prefill-phase overlap policy resolves through ``pcfg``
    (ag_matmul / matmul_rs in the chunk projections)."""
    model = build_model(cfg, pcfg)
    pdt = jnp.dtype(pcfg.param_dtype)
    param_shapes, pspec = model.param_shapes(pdt)
    p_shapes, p_specs = _pool_specs(model, num_pages, page_size, cache_dtype)
    bspec = batch_spec(n_streams, pcfg)
    vec_spec = batch_spec(n_streams, pcfg, extra_dims=0)

    def prefill_chunk_step(params, pools, table_rows, starts, n_valids, tokens):
        return model.prefill_chunk_local(
            params, pools, table_rows, starts, n_valids, tokens)

    jitted = _shard(
        mesh,
        prefill_chunk_step,
        (pspec, p_specs, bspec, vec_spec, vec_spec, bspec),
        (bspec, p_specs),
        donate=(1,),
    )
    in_shapes = (
        param_shapes,
        p_shapes,
        jax.ShapeDtypeStruct((n_streams, pages_per_slot), jnp.int32),
        jax.ShapeDtypeStruct((n_streams,), jnp.int32),
        jax.ShapeDtypeStruct((n_streams,), jnp.int32),
        jax.ShapeDtypeStruct((n_streams, chunk), jnp.int32),
    )
    return BuiltStep(jitted, in_shapes, (pspec, p_specs), model)


def build_prefill_chunk_cp_step(
    cfg: ModelConfig, pcfg: ParallelConfig, mesh,
    *, chunk: int, num_pages: int, page_size: int, pages_per_slot: int,
    cache_dtype=jnp.bfloat16, placement: str = "zigzag",
    cp_attend: str = "ring",
) -> BuiltStep:
    """Context-parallel chunked prefill: ONE stream whose C-token chunk
    shards over the DATA axis by the balanced ``placement`` map — every
    data shard owns C/dp position-ordered rows (zigzag: one early + one
    late half-chunk, equalizing causal work) and the chunk-internal
    attention runs through the balanced ring_attention op
    (``cp_attend="ring"``; ``"dense"`` attends over the gathered pages,
    bit-exact vs the dense path). All inputs replicated: the whole mesh
    cooperates on one request instead of one request per data shard."""
    model = build_model(cfg, pcfg)
    pdt = jnp.dtype(pcfg.param_dtype)
    param_shapes, pspec = model.param_shapes(pdt)
    p_shapes, p_specs = _pool_specs(model, num_pages, page_size, cache_dtype)
    rep1, rep2 = P(None), P(None, None)

    def prefill_chunk_cp_step(params, pools, table_rows, starts, n_valids,
                              tokens):
        return model.prefill_chunk_cp_local(
            params, pools, table_rows, starts, n_valids, tokens,
            placement=placement, cp_attend=cp_attend)

    jitted = _shard(
        mesh,
        prefill_chunk_cp_step,
        (pspec, p_specs, rep2, rep1, rep1, rep2),
        (rep2, p_specs),
        donate=(1,),
    )
    in_shapes = (
        param_shapes,
        p_shapes,
        jax.ShapeDtypeStruct((1, pages_per_slot), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1,), jnp.int32),
        jax.ShapeDtypeStruct((1, chunk), jnp.int32),
    )
    return BuiltStep(jitted, in_shapes, (pspec, p_specs), model)


def build_step(cfg, pcfg, shape, mesh, tcfg=None) -> BuiltStep:
    if shape.kind == "train":
        return build_train_step(cfg, pcfg, shape, mesh, tcfg)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, pcfg, shape, mesh)
    return build_decode_step(cfg, pcfg, shape, mesh)


def default_pcfg(cfg: ModelConfig, shape: ShapeConfig, *, multi_pod: bool,
                 dp: int = 16, tp: int = 16, overlap_mode: str = "ring",
                 overlap_modes=()) -> ParallelConfig:
    """Production parallel config for one (arch x shape x mesh) cell.

    ``overlap_mode="auto"`` asks the analytic tuner for a per-op mode map
    (engine registry candidates) sized to this cell's dominant GEMM;
    explicit ``overlap_modes`` pairs always win over both.
    """
    from ..ops.policy import OverlapPolicy

    kv_shard = "heads"
    if shape.name == "long_500k":
        kv_shard = "sequence"  # distributed flash decode over "data"
    big = cfg.param_count() > 500e9
    moment = "bfloat16" if big else "float32"
    if overlap_mode == "auto":
        from ..core import tuner

        pods_n = 2 if multi_pod else 1
        m = max(tp, shape.tokens // max(1, dp * pods_n))  # rows per data rank
        # the tuner hands back a whole OverlapPolicy — no dict re-packing
        policy = tuner.recommend_overlap_modes(m, cfg.d_model, cfg.d_ff, tp)
    else:
        policy = OverlapPolicy(mode=overlap_mode)
    if overlap_modes:
        policy = policy.with_modes(**dict(overlap_modes))
    return ParallelConfig(
        dp=dp,
        tp=tp,
        pods=2 if multi_pod else 1,
        fsdp=True,
        fsdp_pods=multi_pod,  # 1T-class states only fit when FSDP spans pods
        overlap=policy,
        remat="block",
        moment_dtype=moment,
        kv_shard=kv_shard,
        moe_chunks=8 if (cfg.family == "moe" and cfg.d_model >= 4096) else 1,
    )
