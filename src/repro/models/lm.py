"""Decoder-LM assembly for dense / moe / ssm / hybrid / vlm families.

All forward functions are shard_map-LOCAL (tensors are per-device shards;
collectives are explicit). Layers are scanned in super-blocks (uniform
period) with configurable remat so 100-layer models compile to small HLO.

Layouts:
  train/prefill hidden: (B_loc, S_loc, D)  SP along "model"
  decode hidden:        (B_loc, 1, D)      replicated along "model"
  KV caches:  heads-sharded (B, Hkv_loc, S_max, hd)  [kv_shard="heads"]
              or sequence-sharded over "data" for the paper's distributed
              flash decode [kv_shard="sequence"]
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import obs
from ..configs.base import ModelConfig, ParallelConfig
from ..core import flash_decode as dfd
from ..core import schedules
from ..kernels.flash_decode import page_rows
from . import blocks
from .common import (
    DATA_AXIS,
    MODEL_AXIS,
    POD_AXIS,
    embed_lookup,
    embed_lookup_sp,
    fsdp_get,
    get_params,
    local_linear,
    psum_tp,
    rmsnorm,
    rope,
    sinusoidal_positions,
    vocab_parallel_logits,
    vocab_parallel_loss,
)
from .params import LeafSpec, build_params, spec_tree_shapes, tp_info

Array = jax.Array


def _stack_specs(specs: Dict[str, LeafSpec], n: int) -> Dict[str, LeafSpec]:
    """Give each leaf a leading (n,) dim (sub-layers inside a super-block)."""
    return {
        k: LeafSpec((n,) + s.local_shape, s.tp_sharded, s.init, s.fan_in,
                    s.replica_groups)
        for k, s in specs.items()
    }


def _index_params(p: dict, i: int) -> dict:
    return {k: v[i] for k, v in p.items()}


@dataclass
class LayerPlan:
    n_super: int  # scan length
    period: int  # layers per super-block
    kinds: Tuple[str, ...]


def layer_plan(cfg: ModelConfig) -> LayerPlan:
    if cfg.family in ("dense", "moe"):
        return LayerPlan(cfg.num_layers, 1, ("attn_mlp",))
    if cfg.family == "ssm":
        return LayerPlan(cfg.num_layers, 1, ("ssm",))
    if cfg.family == "hybrid":
        k = cfg.shared_attn_every
        assert cfg.num_layers % k == 0
        return LayerPlan(cfg.num_layers // k, k, ("ssm",) * k + ("shared_attn",))
    if cfg.family == "vlm":
        k = cfg.cross_attn_every
        assert cfg.num_layers % k == 0
        return LayerPlan(cfg.num_layers // k, k, ("self",) * (k - 1) + ("cross",))
    raise ValueError(cfg.family)


class LM:
    """Decoder LM (family in dense/moe/ssm/hybrid/vlm)."""

    def __init__(self, cfg: ModelConfig, pcfg: ParallelConfig):
        self.cfg = cfg
        self.pcfg = pcfg
        self.info = tp_info(cfg, pcfg)
        self.plan = layer_plan(cfg)
        self._build_specs()

    # ------------------------------------------------------------------
    def _ffn_specs(self):
        cfg, info = self.cfg, self.info
        return (
            blocks.moe_specs(cfg, info)
            if cfg.family == "moe"
            else blocks.mlp_specs(cfg, info)
        )

    def _build_specs(self):
        cfg, info = self.cfg, self.info
        plan = self.plan
        layer: Dict[str, Dict[str, LeafSpec]] = {}
        if cfg.family in ("dense", "moe"):
            layer["attn"] = blocks.attention_specs(cfg, info)
            layer["ffn"] = self._ffn_specs()
        elif cfg.family == "ssm":
            layer["ssm"] = _stack_specs(blocks.ssm_specs(cfg, info), 1)
        elif cfg.family == "hybrid":
            layer["ssm"] = _stack_specs(blocks.ssm_specs(cfg, info), plan.period)
        elif cfg.family == "vlm":
            k = plan.period
            layer["attn"] = _stack_specs(blocks.attention_specs(cfg, info), k - 1)
            layer["cross"] = blocks.attention_specs(cfg, info, cross=True)
            layer["ffn"] = _stack_specs(blocks.mlp_specs(cfg, info), k)
        self.layer_specs = layer

        top: Dict[str, Any] = {
            "embed": LeafSpec((info.vocab_loc, cfg.d_model), fan_in=cfg.d_model),
            "ln_f": LeafSpec((cfg.d_model,), tp_sharded=False, init="ones"),
        }
        if not cfg.tie_embeddings:
            top["unembed"] = LeafSpec((info.vocab_loc, cfg.d_model), fan_in=cfg.d_model)
        if cfg.family == "hybrid":
            top["shared_attn"] = blocks.attention_specs(cfg, info)
            top["shared_mlp"] = blocks.mlp_specs(cfg, info)
        if cfg.family == "vlm":
            top["vision_proj"] = LeafSpec(
                (cfg.vision_dim, cfg.d_model), tp_sharded=False, fan_in=cfg.vision_dim
            )
        self.top_specs = top

    def init(self, key, dtype=jnp.bfloat16):
        k1, k2 = jax.random.split(key)
        top, top_sp = build_params(self.top_specs, k1, self.pcfg, dtype=dtype)
        lay, lay_sp = build_params(
            self.layer_specs, k2, self.pcfg, layers=self.plan.n_super, dtype=dtype
        )
        return {"top": top, "layers": lay}, {"top": top_sp, "layers": lay_sp}

    def param_shapes(self, dtype=jnp.bfloat16):
        top, top_sp = spec_tree_shapes(self.top_specs, self.pcfg, dtype=dtype)
        lay, lay_sp = spec_tree_shapes(
            self.layer_specs, self.pcfg, layers=self.plan.n_super, dtype=dtype
        )
        return {"top": top, "layers": lay}, {"top": top_sp, "layers": lay_sp}

    # ------------------------------------------------------------------
    def _unpack_layer(self, p_layer: dict) -> dict:
        """Packed per-super-block leaves -> logical tensors (FSDP gather)."""
        return {
            grp: get_params(p_layer[grp], self.layer_specs[grp], self.pcfg)
            for grp in self.layer_specs
        }

    def _unpack_top(self, params: dict, *names) -> dict:
        return {
            n: get_params(params["top"][n], self.top_specs[n], self.pcfg)
            for n in names
            if n in params["top"]
        }

    def _ckpt(self, fn):
        """remat="nested": additionally checkpoint each sub-block so the
        backward live-set is one sub-block's internals, not a whole
        super-block's (2-level remat for the 90B/1T-class models)."""
        return jax.checkpoint(fn) if self.pcfg.remat == "nested" else fn

    def _super_block_train(self, pl: dict, h: Array, shared: dict,
                           cross_src: Optional[Array]):
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        attn = self._ckpt(
            lambda p_, h_: blocks.attention_train(cfg, pcfg, info, p_, h_)
        )
        mlp = self._ckpt(lambda p_, h_: blocks.mlp_train(cfg, pcfg, info, p_, h_))
        moe = self._ckpt(lambda p_, h_: blocks.moe_train(
            cfg, pcfg, info, p_, h_, cfg.capacity_factor))
        ssm = self._ckpt(lambda p_, h_: blocks.ssm_train(cfg, pcfg, info, p_, h_))
        cross = self._ckpt(
            lambda p_, h_, src: blocks.attention_train(
                cfg, pcfg, info, p_, h_, cross_src=src
            )
        )
        if cfg.family in ("dense", "moe"):
            if cfg.family == "dense" and blocks.boundary_fused(pcfg):
                # policy turned the attention->MLP seam into the fused
                # rs->ag boundary op — route the pair as one unit
                pair = self._ckpt(
                    lambda pa, pf, h_: blocks.attn_mlp_train(
                        cfg, pcfg, info, pa, pf, h_))
                h = pair(pl["attn"], pl["ffn"], h)
            else:
                h = attn(pl["attn"], h)
                h = moe(pl["ffn"], h) if cfg.family == "moe" else mlp(pl["ffn"], h)
        elif cfg.family == "ssm":
            h = ssm(_index_params(pl["ssm"], 0), h)
        elif cfg.family == "hybrid":
            for i in range(self.plan.period):
                h = ssm(_index_params(pl["ssm"], i), h)
            h = attn(shared["shared_attn"], h)
            h = mlp(shared["shared_mlp"], h)
        elif cfg.family == "vlm":
            k = self.plan.period
            for i in range(k - 1):
                h = attn(_index_params(pl["attn"], i), h)
                h = mlp(_index_params(pl["ffn"], i), h)
            h = cross(pl["cross"], h, cross_src)
            h = mlp(_index_params(pl["ffn"], k - 1), h)
        return h

    def _remat(self, fn):
        if self.pcfg.remat == "none":
            return fn
        if self.pcfg.remat == "dots":
            return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
        if self.pcfg.remat == "block_save_ag":
            # keep AG+GEMM products across backward: no recompute of the
            # gather rings (-1/3 collective volume, +activation memory)
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies.save_only_these_names("ag_out")
            )
        return jax.checkpoint(fn)  # "block" and the outer level of "nested"

    def _backbone_train(self, params: dict, h: Array, cross_src: Optional[Array]):
        shared = self._unpack_top(params, "shared_attn", "shared_mlp")

        def body(carry, xs):
            pl = self._unpack_layer(xs)
            return self._super_block_train(pl, carry, shared, cross_src), None

        body = self._remat(body)
        h, _ = lax.scan(body, h, params["layers"])
        return h

    # ------------------------------------------------------------------
    def loss_local(
        self,
        params: dict,
        tokens: Array,  # (B_loc, S) int32
        labels: Array,  # (B_loc, S) int32, -1 = pad
        extra: Optional[dict] = None,  # e.g. {"vision": (B, Tv, D_vis)}
    ) -> Array:
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        b, s = tokens.shape
        tp = pcfg.tp
        s_loc = s // tp
        me = lax.axis_index(MODEL_AXIS)
        lbl_sp = lax.dynamic_slice(labels, (0, me * s_loc), (b, s_loc))

        cdt = jnp.dtype(pcfg.compute_dtype)
        embed = fsdp_get(params["top"]["embed"], self.top_specs["embed"], pcfg, cdt)
        h = embed_lookup_sp(tokens, embed, info, tp)
        if not cfg.use_rope:
            pos = me * s_loc + jnp.arange(s_loc)
            h = h + sinusoidal_positions(pos, cfg.d_model)[None].astype(h.dtype)

        cross_src = None
        if cfg.family == "vlm":
            vis = extra["vision"]  # (B, Tv, D_vis)
            wproj = fsdp_get(
                params["top"]["vision_proj"], self.top_specs["vision_proj"], pcfg, cdt
            )
            cross_src = local_linear(
                vis.reshape(-1, vis.shape[-1]).astype(cdt), wproj
            ).reshape(vis.shape[0], vis.shape[1], cfg.d_model)

        h = self._backbone_train(params, h, cross_src)

        ln_f = fsdp_get(params["top"]["ln_f"], self.top_specs["ln_f"], pcfg, h.dtype)
        h = rmsnorm(h, ln_f, cfg.norm_eps).reshape(b * s_loc, cfg.d_model)
        un_name = "embed" if cfg.tie_embeddings else "unembed"
        w_out = fsdp_get(params["top"][un_name], self.top_specs[un_name], pcfg, h.dtype).T
        loss_sum, count = vocab_parallel_loss(
            h, w_out, lbl_sp.reshape(-1), info, cfg.vocab_size
        )
        axes = (
            (MODEL_AXIS, DATA_AXIS)
            if pcfg.pods == 1
            else (MODEL_AXIS, DATA_AXIS, "pod")
        )
        total = lax.psum(loss_sum, axes)
        n = lax.psum(count, axes)
        return total / jnp.maximum(n, 1.0)

    def prefill_logits_local(
        self, params: dict, tokens: Array, extra: Optional[dict] = None
    ) -> Array:
        """Forward-only inference prefill: last-token logits (B, vocab)."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        b, s = tokens.shape
        tp = pcfg.tp
        s_loc = s // tp
        me = lax.axis_index(MODEL_AXIS)
        cdt = jnp.dtype(pcfg.compute_dtype)
        embed = fsdp_get(params["top"]["embed"], self.top_specs["embed"], pcfg, cdt)
        h = embed_lookup_sp(tokens, embed, info, tp)
        if not cfg.use_rope:
            pos = me * s_loc + jnp.arange(s_loc)
            h = h + sinusoidal_positions(pos, cfg.d_model)[None].astype(h.dtype)
        cross_src = None
        if cfg.family == "vlm":
            vis = extra["vision"]
            wproj = fsdp_get(
                params["top"]["vision_proj"], self.top_specs["vision_proj"], pcfg, cdt
            )
            cross_src = local_linear(
                vis.reshape(-1, vis.shape[-1]).astype(cdt), wproj
            ).reshape(vis.shape[0], vis.shape[1], cfg.d_model)
        h = self._backbone_train(params, h, cross_src)
        ln_f = fsdp_get(params["top"]["ln_f"], self.top_specs["ln_f"], pcfg, h.dtype)
        # the TRUE last token lives on the last model rank's SP window;
        # vocab_parallel_logits column-gathers per-rank partials, so its
        # input must be TP-replicated — replicate that row FIRST (a
        # post-hoc mask of the gathered logits cannot unmix the columns
        # the other ranks contributed from their own windows)
        keep = (me == tp - 1).astype(h.dtype)
        h_last = lax.psum(h[:, -1, :] * keep, MODEL_AXIS)
        h_last = rmsnorm(h_last, ln_f, cfg.norm_eps)
        un_name = "embed" if cfg.tie_embeddings else "unembed"
        w_out = fsdp_get(params["top"][un_name], self.top_specs[un_name], pcfg,
                         h.dtype).T
        return vocab_parallel_logits(h_last, w_out, info, cfg.vocab_size)

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def _kv_seq_sharded(self) -> bool:
        return self.pcfg.kv_shard == "sequence"

    def cache_shapes(self, batch_local: int, s_max: int, dtype=jnp.bfloat16):
        """ShapeDtypeStructs for decode state, stacked over n_super."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        n = self.plan.n_super
        hd = cfg.head_dim
        s_kv = s_max // pcfg.dp if self._kv_seq_sharded() else s_max

        def kv(n_sub=None):
            shape = (batch_local, info.hkv_loc, s_kv, hd)
            if n_sub is not None:
                shape = (n_sub,) + shape
            return {
                "k": jax.ShapeDtypeStruct((n,) + shape, dtype),
                "v": jax.ShapeDtypeStruct((n,) + shape, dtype),
            }

        def ssm_state(n_sub):
            conv_ch = info.di_loc + 2 * cfg.ssm_num_groups * cfg.ssm_state
            return {
                "conv": jax.ShapeDtypeStruct(
                    (n, n_sub, batch_local, cfg.ssm_conv_width - 1, conv_ch), dtype
                ),
                "ssd": jax.ShapeDtypeStruct(
                    (n, n_sub, batch_local, info.nh_loc, cfg.ssm_head_dim, cfg.ssm_state),
                    jnp.float32,
                ),
            }

        fam = cfg.family
        if fam in ("dense", "moe"):
            return {"attn": kv()}
        if fam == "ssm":
            return {"ssm": ssm_state(1)}
        if fam == "hybrid":
            return {"ssm": ssm_state(self.plan.period), "attn": kv()}
        if fam == "vlm":
            k = self.plan.period
            tv = cfg.vision_tokens
            return {
                "attn": kv(k - 1),
                "cross_k": jax.ShapeDtypeStruct(
                    (n, batch_local, info.hkv_loc, tv, hd), dtype
                ),
                "cross_v": jax.ShapeDtypeStruct(
                    (n, batch_local, info.hkv_loc, tv, hd), dtype
                ),
            }
        raise ValueError(fam)

    def paged_cache_shapes(self, num_pages: int, page_size: int,
                           dtype=jnp.bfloat16):
        """ShapeDtypeStructs for the paged decode pools (dense/moe,
        heads-sharded KV), stacked over n_super like cache_shapes; each
        head's page stored as ``page_rows`` lays it out."""
        cfg, info = self.cfg, self.info
        assert cfg.family in ("dense", "moe"), cfg.family
        assert not self._kv_seq_sharded(), "paged KV is heads-sharded"
        n = self.plan.n_super
        shape = (n, num_pages, info.hkv_loc,
                 *page_rows(page_size, cfg.head_dim))
        return {"attn": {"k": jax.ShapeDtypeStruct(shape, dtype),
                         "v": jax.ShapeDtypeStruct(shape, dtype)}}

    def _scan_paged(self, body, h, params: dict, pools: dict):
        """Run ``body((h, pool_k, pool_v), (layer_params, layer))`` over the
        layers. The stacked pools ride in the carry: each layer writes and
        reads its own pages in place at its index, and no layer's pool is
        sliced out or stacked back. Returns (h, pool_k, pool_v)."""
        xs = (params["layers"], jnp.arange(self.plan.n_super, dtype=jnp.int32))
        (h, pk, pv), _ = lax.scan(
            body, (h, pools["attn"]["k"], pools["attn"]["v"]), xs)
        return h, pk, pv

    def decode_step_paged_local(
        self,
        params: dict,
        pools: dict,     # paged_cache_shapes tree
        table: Array,    # (B_loc, P) int32 page ids
        lengths: Array,  # (B_loc,) tokens already cached per slot
        active: Array,   # (B_loc,) bool — idle lanes write to scratch
        token: Array,    # (B_loc, 1) int32
    ) -> Tuple[Array, dict]:
        """One decode step against the paged KV pools (serve/kvcache.py).
        Inactive lanes produce garbage logits and scratch-page writes;
        the engine ignores both."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        assert cfg.family in ("dense", "moe"), cfg.family
        b = token.shape[0]
        cdt = jnp.dtype(pcfg.compute_dtype)
        with obs.scope("embed"):
            embed = fsdp_get(params["top"]["embed"], self.top_specs["embed"],
                             pcfg, cdt)
            h = embed_lookup(token, embed, info)  # (B, 1, D)
            if not cfg.use_rope:
                h = h + sinusoidal_positions(
                    lengths, cfg.d_model)[:, None, :].astype(h.dtype)

        def body(carry, xs):
            hh, pk, pv = carry
            p_layer, layer = xs
            pl = self._unpack_layer(p_layer)
            hh, pk, pv = blocks.attention_decode_paged(
                cfg, pcfg, info, pl["attn"], hh, pk, pv, layer, table,
                lengths, active)
            if cfg.family == "moe":
                hh = blocks.moe_decode(cfg, pcfg, info, pl["ffn"], hh)
            else:
                hh = blocks.mlp_decode(cfg, pcfg, info, pl["ffn"], hh)
            return (hh, pk, pv), None

        with obs.scope("layers"):
            h, pk, pv = self._scan_paged(body, h, params, pools)
        with obs.scope("logits"):
            ln_f = fsdp_get(params["top"]["ln_f"], self.top_specs["ln_f"],
                            pcfg, h.dtype)
            h = rmsnorm(h, ln_f, cfg.norm_eps).reshape(b, cfg.d_model)
            un_name = "embed" if cfg.tie_embeddings else "unembed"
            w_out = fsdp_get(params["top"][un_name], self.top_specs[un_name],
                             pcfg, h.dtype).T
            logits = vocab_parallel_logits(h, w_out, info, cfg.vocab_size)
        return logits, {"attn": {"k": pk, "v": pv}}

    def prefill_chunk_local(
        self,
        params: dict,
        pools: dict,       # paged_cache_shapes tree
        table_row: Array,  # (1, P) int32 — ONE request's block table
        start: Array,      # (1,) int32 absolute position of the chunk
        n_valid: Array,    # (1,) int32 real tokens in the chunk (0 = idle)
        tokens: Array,     # (1, C) int32 chunk tokens, right-padded
    ) -> Tuple[Array, dict]:
        """Chunked prefill: C prompt tokens of ONE request (per data
        shard) in a single SP forward, K/V written into the paged pools,
        last-valid-token logits out — the serving fast path vs
        token-by-token decode ingestion. The leading dim is the local
        slice of the per-data-shard request stream (always 1)."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        assert cfg.family in ("dense", "moe"), cfg.family
        assert not self._kv_seq_sharded(), "chunked prefill is heads-sharded"
        row = table_row[0]
        start = start[0]
        n_valid = n_valid[0]
        b, s = tokens.shape  # (1, C)
        tp = pcfg.tp
        s_loc = s // tp
        me = lax.axis_index(MODEL_AXIS)
        cdt = jnp.dtype(pcfg.compute_dtype)
        with obs.scope("embed"):
            embed = fsdp_get(params["top"]["embed"], self.top_specs["embed"],
                             pcfg, cdt)
            h = embed_lookup_sp(tokens, embed, info, tp)
            if not cfg.use_rope:
                pos = start + me * s_loc + jnp.arange(s_loc)
                h = h + sinusoidal_positions(
                    pos, cfg.d_model)[None].astype(h.dtype)

        def body(carry, xs):
            hh, pk, pv = carry
            p_layer, layer = xs
            pl = self._unpack_layer(p_layer)
            hh, pk, pv = blocks.attention_prefill_chunk(
                cfg, pcfg, info, pl["attn"], hh, pk, pv, layer, row, start,
                n_valid)
            if cfg.family == "moe":
                hh = blocks.moe_train(cfg, pcfg, info, pl["ffn"], hh, None)
            else:
                hh = blocks.mlp_train(cfg, pcfg, info, pl["ffn"], hh)
            return (hh, pk, pv), None

        with obs.scope("layers"):
            h, pk, pv = self._scan_paged(self._remat(body), h, params, pools)
        with obs.scope("logits"):
            ln_f = fsdp_get(params["top"]["ln_f"], self.top_specs["ln_f"],
                            pcfg, h.dtype)
            # logits of the LAST VALID chunk token (the next-token logits
            # when this is the prompt's final chunk); it lives on model rank
            # idx // s_loc — replicate that row over TP before the
            # vocab-parallel projection (see prefill_logits_local)
            idx = jnp.maximum(n_valid - 1, 0)
            local_idx = jnp.clip(idx - me * s_loc, 0, s_loc - 1)
            h_sel = lax.dynamic_slice(
                h, (0, local_idx, 0), (b, 1, cfg.d_model))[:, 0]
            keep = (me == idx // s_loc).astype(h.dtype)
            h_last = lax.psum(h_sel * keep, MODEL_AXIS)
            h_last = rmsnorm(h_last, ln_f, cfg.norm_eps)
            un_name = "embed" if cfg.tie_embeddings else "unembed"
            w_out = fsdp_get(params["top"][un_name], self.top_specs[un_name],
                             pcfg, h.dtype).T
            logits = vocab_parallel_logits(h_last, w_out, info, cfg.vocab_size)
        return logits, {"attn": {"k": pk, "v": pv}}

    def prefill_chunk_cp_local(
        self,
        params: dict,
        pools: dict,       # paged_cache_shapes tree
        table_row: Array,  # (1, P) int32 — ONE request's block table
        start: Array,      # (1,) int32 absolute position of the chunk
        n_valid: Array,    # (1,) int32 real tokens in the chunk (0 = idle)
        tokens: Array,     # (1, C) int32 chunk tokens, right-padded, replicated
        *,
        placement: str = "zigzag",
        cp_attend: str = "ring",
    ) -> Tuple[Array, dict]:
        """Context-parallel chunked prefill: ONE request's C-token chunk
        sharded over the DATA axis by the balanced placement map — every
        data shard owns C/dp position-ordered chunk rows (zigzag: one
        early + one late half-chunk, equalizing causal attention work)
        and runs the SP/TP projections on its rows only; chunk K/V
        merges into the paged pools via the same scatter-by-table write
        on every rank (pool replicas stay bitwise equal to the dense
        path). All inputs are replicated (the whole mesh cooperates on
        one stream instead of one stream per data shard)."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        assert cfg.family in ("dense", "moe"), cfg.family
        assert not self._kv_seq_sharded(), "chunked prefill is heads-sharded"
        assert pcfg.pods == 1, "cp prefill shards the chunk over 'data' only"
        row = table_row[0]
        start = start[0]
        n_valid = n_valid[0]
        b, c = tokens.shape  # (1, C)
        tp = pcfg.tp
        cp = pcfg.dp
        assert c % (cp * tp) == 0, (c, cp, tp)
        s_cp = c // cp
        s_loc = s_cp // tp
        if placement == "zigzag" and s_cp % 2:
            placement = "contiguous"
        # static owner maps: chunk row <-> (cp rank, local slot)
        rows_np = np.stack([schedules.placement_rows(placement, cp, r, s_cp)
                            for r in range(cp)])
        table = jnp.asarray(rows_np, jnp.int32)
        inv_perm = jnp.asarray(np.argsort(rows_np.reshape(-1), kind="stable"),
                               jnp.int32)  # rank-major gather -> position order
        ci = lax.axis_index(DATA_AXIS)
        me = lax.axis_index(MODEL_AXIS)
        rows_own = table[ci]  # (C/cp,) global chunk-row indices
        toks_own = jnp.take(tokens, rows_own, axis=1)  # (1, C/cp)
        cdt = jnp.dtype(pcfg.compute_dtype)
        embed = fsdp_get(params["top"]["embed"], self.top_specs["embed"], pcfg, cdt)
        h = embed_lookup_sp(toks_own, embed, info, tp)  # (1, C/(cp*tp), D)
        if not cfg.use_rope:
            pos_loc = start + lax.dynamic_slice(rows_own, (me * s_loc,), (s_loc,))
            h = h + sinusoidal_positions(pos_loc, cfg.d_model)[None].astype(h.dtype)

        def body(carry, xs):
            hh, pk, pv = carry
            p_layer, layer = xs
            pl = self._unpack_layer(p_layer)
            hh, pk, pv = blocks.attention_prefill_chunk_cp(
                cfg, pcfg, info, pl["attn"], hh, pk, pv, layer, row, start,
                n_valid, rows_own, inv_perm, placement=placement,
                cp_attend=cp_attend)
            if cfg.family == "moe":
                hh = blocks.moe_train(cfg, pcfg, info, pl["ffn"], hh, None)
            else:
                hh = blocks.mlp_train(cfg, pcfg, info, pl["ffn"], hh)
            return (hh, pk, pv), None

        h, pk, pv = self._scan_paged(self._remat(body), h, params, pools)
        ln_f = fsdp_get(params["top"]["ln_f"], self.top_specs["ln_f"], pcfg, h.dtype)
        # last-valid-token logits: the row lives on exactly one (cp, tp)
        # rank under the placement map — one-hot select, then replicate
        # over BOTH axes (adding exact zeros keeps it bit-equal to the
        # dense path's model-axis psum)
        idx = jnp.maximum(n_valid - 1, 0)
        loc_rows = lax.dynamic_slice(rows_own, (me * s_loc,), (s_loc,))
        keep = (loc_rows == idx).astype(h.dtype)  # (s_loc,)
        h_sel = jnp.sum(h * keep[None, :, None], axis=1)  # (1, D)
        h_last = lax.psum(h_sel, (DATA_AXIS, MODEL_AXIS))
        h_last = rmsnorm(h_last, ln_f, cfg.norm_eps)
        un_name = "embed" if cfg.tie_embeddings else "unembed"
        w_out = fsdp_get(params["top"][un_name], self.top_specs[un_name], pcfg,
                         h.dtype).T
        logits = vocab_parallel_logits(h_last, w_out, info, cfg.vocab_size)
        return logits, {"attn": {"k": pk, "v": pv}}

    def decode_step_local(
        self,
        params: dict,
        caches: dict,
        cache_len: Array,  # scalar int32, or per-slot (B_loc,) int32
        token: Array,  # (B_loc, 1) int32
    ) -> Tuple[Array, dict]:
        """One decode step. Returns (logits (B_loc, vocab), new caches).

        ``cache_len`` may be per-slot so continuously batched slots
        advance independently (scalar = all slots in lockstep; the
        sequence-sharded distributed-flash-decode path is scalar-only).
        A per-slot vector arrives REPLICATED at the global batch size
        (its in_spec is shared with the scalar form) — each data shard
        slices its own (B_loc,) window here."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        b = token.shape[0]
        if jnp.ndim(cache_len) == 1 and cache_len.shape[0] != b:
            shard = lax.axis_index(DATA_AXIS)
            if pcfg.pods > 1:
                shard = lax.axis_index(POD_AXIS) * pcfg.dp + shard
            cache_len = lax.dynamic_slice(
                jnp.asarray(cache_len, jnp.int32), (shard * b,), (b,))
        cdt = jnp.dtype(pcfg.compute_dtype)
        embed = fsdp_get(params["top"]["embed"], self.top_specs["embed"], pcfg, cdt)
        h = embed_lookup(token, embed, info)  # (B, 1, D)
        if not cfg.use_rope:
            pos = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
            h = h + sinusoidal_positions(pos, cfg.d_model)[:, None, :].astype(h.dtype)

        shared = self._unpack_top(params, "shared_attn", "shared_mlp")

        def body(carry, xs):
            hh = carry
            p_layer, cache = xs
            pl = self._unpack_layer(p_layer)
            hh, new_cache = self._super_block_decode(pl, cache, hh, cache_len, shared)
            return hh, new_cache

        h, new_caches = lax.scan(body, h, (params["layers"], caches))
        ln_f = fsdp_get(params["top"]["ln_f"], self.top_specs["ln_f"], pcfg, h.dtype)
        h = rmsnorm(h, ln_f, cfg.norm_eps).reshape(b, cfg.d_model)
        un_name = "embed" if cfg.tie_embeddings else "unembed"
        w_out = fsdp_get(params["top"][un_name], self.top_specs[un_name], pcfg, h.dtype).T
        logits = vocab_parallel_logits(h, w_out, info, cfg.vocab_size)
        return logits, new_caches

    def _attn_decode_dispatch(self, pl, h, cache, cache_len, cross_kv=None):
        """Heads-sharded local decode, or the paper's distributed flash
        decode when the KV cache is sequence-sharded over "data"."""
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        if not self._kv_seq_sharded() or cross_kv is not None:
            return blocks.attention_decode(
                cfg, pcfg, info, pl, h, cache["k"], cache["v"], cache_len,
                cross_kv=cross_kv,
            )
        # sequence-sharded KV over the data axis: distributed flash decode
        if jnp.ndim(cache_len) != 0:
            raise ValueError(
                "sequence-sharded KV decode takes a scalar cache_len; "
                "per-slot lengths need kv_shard='heads' (or the paged path)")
        b, _, d = h.shape
        hd = cfg.head_dim
        pp = blocks._get_attn(pl, h.dtype)
        hh = rmsnorm(h, pp.ln, cfg.norm_eps).reshape(b, d)
        q = local_linear(hh, pp.wq, pp.bq).reshape(b, info.hq_loc, hd)
        kv = local_linear(hh, pp.wkv, pp.bkv).reshape(b, 2, info.hkv_loc, hd)
        k_new, v_new = kv[:, 0], kv[:, 1]
        if cfg.use_rope:
            posq = jnp.full((b, 1), cache_len, jnp.int32)
            q = rope(q[:, None], posq, cfg.rope_theta)[:, 0]
            k_new = rope(k_new[:, None], posq, cfg.rope_theta)[:, 0]
        s_shard = cache["k"].shape[2]
        me_d = lax.axis_index(DATA_AXIS)
        local_pos = cache_len - me_d * s_shard
        owns = (local_pos >= 0) & (local_pos < s_shard)
        safe = jnp.clip(local_pos, 0, s_shard - 1)
        upd_k = lax.dynamic_update_slice(
            cache["k"], k_new[:, :, None, :].astype(cache["k"].dtype), (0, 0, safe, 0)
        )
        ck = jnp.where(owns, upd_k, cache["k"])
        upd_v = lax.dynamic_update_slice(
            cache["v"], v_new[:, :, None, :].astype(cache["v"].dtype), (0, 0, safe, 0)
        )
        cv = jnp.where(owns, upd_v, cache["v"])
        valid = jnp.clip(cache_len + 1 - me_d * s_shard, 0, s_shard)
        lengths = jnp.full((b,), valid, jnp.int32)
        fd = pcfg.policy.resolve("flash_decode")
        o = dfd.distributed_flash_decode(q, ck, cv, lengths, DATA_AXIS,
                                         mode=fd.mode, backend=fd.backend)
        o = o.astype(h.dtype).reshape(b, info.hq_loc * hd)
        out = psum_tp(local_linear(o, pp.wo), pcfg)
        return h + out.reshape(b, 1, d), ck, cv

    def _super_block_decode(self, pl, cache, h, cache_len, shared):
        cfg, pcfg, info = self.cfg, self.pcfg, self.info
        if cfg.family in ("dense", "moe"):
            h, ck, cv = self._attn_decode_dispatch(pl["attn"], h, cache["attn"], cache_len)
            new_cache = {"attn": {"k": ck, "v": cv}}
            if cfg.family == "moe":
                h = blocks.moe_decode(cfg, pcfg, info, pl["ffn"], h)
            else:
                h = blocks.mlp_decode(cfg, pcfg, info, pl["ffn"], h)
        elif cfg.family == "ssm":
            h, conv, ssd = blocks.ssm_decode(
                cfg, pcfg, info, _index_params(pl["ssm"], 0), h,
                cache["ssm"]["conv"][0], cache["ssm"]["ssd"][0],
            )
            new_cache = {"ssm": {"conv": conv[None], "ssd": ssd[None]}}
        elif cfg.family == "hybrid":
            convs, ssds = [], []
            for i in range(self.plan.period):
                h, conv, ssd = blocks.ssm_decode(
                    cfg, pcfg, info, _index_params(pl["ssm"], i), h,
                    cache["ssm"]["conv"][i], cache["ssm"]["ssd"][i],
                )
                convs.append(conv)
                ssds.append(ssd)
            h, ck, cv = self._attn_decode_dispatch(
                shared["shared_attn"], h, cache["attn"], cache_len
            )
            h = blocks.mlp_decode(cfg, pcfg, info, shared["shared_mlp"], h)
            new_cache = {
                "ssm": {"conv": jnp.stack(convs), "ssd": jnp.stack(ssds)},
                "attn": {"k": ck, "v": cv},
            }
        elif cfg.family == "vlm":
            k = self.plan.period
            ks, vs = [], []
            for i in range(k - 1):
                h, ck, cv = blocks.attention_decode(
                    cfg, pcfg, info, _index_params(pl["attn"], i), h,
                    cache["attn"]["k"][i], cache["attn"]["v"][i], cache_len,
                )
                ks.append(ck)
                vs.append(cv)
                h = blocks.mlp_decode(cfg, pcfg, info, _index_params(pl["ffn"], i), h)
            h, _, _ = blocks.attention_decode(
                cfg, pcfg, info, pl["cross"], h,
                cache["cross_k"], cache["cross_v"], cache_len,
                cross_kv=(cache["cross_k"], cache["cross_v"]),
            )
            h = blocks.mlp_decode(cfg, pcfg, info, _index_params(pl["ffn"], k - 1), h)
            new_cache = {
                "attn": {"k": jnp.stack(ks), "v": jnp.stack(vs)},
                "cross_k": cache["cross_k"],
                "cross_v": cache["cross_v"],
            }
        return h, new_cache
