"""Model blocks (attention / MLP / MoE / Mamba2-SSD), shard_map-local.

Every SP->TP boundary is an overlapped AllGather-GEMM and every TP->SP
boundary an overlapped GEMM-ReduceScatter (the paper's technique as the
model's default projection path). Decode-time blocks use replicated
single-token activations with local projections + one small psum — the
small-message regime the paper serves with low-latency kernels.

Blocks take LOGICAL (already unpacked, see common.get_params) parameter
dicts; packing/FSDP-gather happens in the caller so stacked sub-layer
leaves can be indexed per sub-layer.

Row-order convention: a sequence-parallel tensor (B, S_loc, D) flattens to
(B*S_loc, D); the gathered full-sequence layout is rank-major
(tp, B, S_loc, ...). `_sp_gathered_to_bsd` / `_bsd_to_sp_rows` convert.
"""
from __future__ import annotations

import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from .. import ops as oplib
from ..configs.base import ModelConfig, ParallelConfig
from ..core import collective_matmul as cm
from ..core import moe_overlap as mo
from ..kernels import ops
from ..kernels import ref as kref
from .common import (
    DATA_AXIS,
    MODEL_AXIS,
    activation,
    ag_linear,
    local_linear,
    psum_tp,
    rmsnorm,
    rope,
    rs_linear,
)
from .params import LeafSpec, TPInfo

Array = jax.Array


def _sp_gathered_to_bsd(y: Array, tp: int, b: int, s_loc: int) -> Array:
    """(tp*B*S_loc, C) rank-major -> (B, S, C)."""
    c = y.shape[-1]
    return (
        y.reshape(tp, b, s_loc, c).transpose(1, 0, 2, 3).reshape(b, tp * s_loc, c)
    )


def _bsd_to_sp_rows(x: Array, tp: int) -> Array:
    """(B, S, C) -> (tp*B*S_loc, C) rank-major rows for GEMM+RS."""
    b, s, c = x.shape
    s_loc = s // tp
    return x.reshape(b, tp, s_loc, c).transpose(1, 0, 2, 3).reshape(tp * b * s_loc, c)


# ===========================================================================
# Attention
# ===========================================================================


def attention_specs(
    cfg: ModelConfig, info: TPInfo, *, cross: bool = False, kv_dim: Optional[int] = None
) -> Dict[str, LeafSpec]:
    d, hd = cfg.d_model, cfg.head_dim
    kv_dim = kv_dim or d
    specs = {
        "ln": LeafSpec((d,), tp_sharded=False, init="ones"),
        "wq": LeafSpec((d, info.hq_loc * hd), fan_in=d),
        "wkv": LeafSpec(
            (kv_dim, 2 * info.hkv_loc * hd), fan_in=kv_dim,
            replica_groups=info.kv_rep,
        ),
        "wo": LeafSpec((info.hq_loc * hd, d), fan_in=info.hq_loc * hd * info.tp),
    }
    if cfg.qkv_bias:
        specs["bq"] = LeafSpec((info.hq_loc * hd,), init="zeros")
        specs["bkv"] = LeafSpec(
            (2 * info.hkv_loc * hd,), init="zeros", replica_groups=info.kv_rep
        )
    return specs


class AttnParams(NamedTuple):
    ln: Array
    wq: Array
    wkv: Array
    wo: Array
    bq: Optional[Array]
    bkv: Optional[Array]


def _get_attn(p: dict, dtype) -> AttnParams:
    def c(n):
        return p[n].astype(dtype) if n in p else None

    return AttnParams(
        ln=c("ln"), wq=c("wq"), wkv=c("wkv"), wo=c("wo"), bq=c("bq"), bkv=c("bkv")
    )


def _attn_core(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    pp: AttnParams,
    x_sp: Array,  # (B, S_loc, D)
    *,
    causal: bool = True,
    cross_src: Optional[Array] = None,  # (B, T_src, D) replicated over tp
):
    """Attention up to (excluding) the output projection: norm, fused
    QKV AG+GEMM, rope, flash attention. Returns the context as rank-major
    TP rows (tp*B*S_loc, Hq_loc*hd) — ready for ``rs_linear(.., wo)`` or
    the fused boundary op — plus (k, v) in cache layout."""
    b, s_loc, d = x_sp.shape
    tp = pcfg.tp
    s = s_loc * tp
    hd = cfg.head_dim

    h = rmsnorm(x_sp, pp.ln, cfg.norm_eps).reshape(b * s_loc, d)
    # SP -> TP: one fused AG+GEMM for q and kv (single gather of the tokens)
    if cross_src is None:
        wqkv = jnp.concatenate([pp.wq, pp.wkv], axis=1)
        bqkv = jnp.concatenate([pp.bq, pp.bkv]) if pp.bq is not None else None
        y = ag_linear(h, wqkv, pcfg, bqkv)  # (tp*B*S_loc, cols)
        y = _sp_gathered_to_bsd(y, tp, b, s_loc)  # (B, S, cols)
        q, kv = jnp.split(y, [info.hq_loc * hd], axis=-1)
        k, v = jnp.split(kv, 2, axis=-1)
        src_len = s
    else:
        q = ag_linear(h, pp.wq, pcfg, pp.bq)
        q = _sp_gathered_to_bsd(q, tp, b, s_loc)
        kv = local_linear(cross_src.reshape(-1, cross_src.shape[-1]), pp.wkv, pp.bkv)
        kv = kv.reshape(b, cross_src.shape[1], -1)
        k, v = jnp.split(kv, 2, axis=-1)
        src_len = cross_src.shape[1]

    q = q.reshape(b, s, info.hq_loc, hd)
    k = k.reshape(b, src_len, info.hkv_loc, hd)
    v = v.reshape(b, src_len, info.hkv_loc, hd)
    if cfg.use_rope and cross_src is None:
        pos = jnp.arange(s)
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)

    o = ops.flash_attention(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal and cross_src is None,
    )  # (B, Hq_loc, S, hd)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, info.hq_loc * hd)
    return (_bsd_to_sp_rows(o, tp),
            (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)))


def attention_train(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    p: dict,  # logical tensors
    x_sp: Array,  # (B, S_loc, D)
    *,
    causal: bool = True,
    cross_src: Optional[Array] = None,  # (B, T_src, D) replicated over tp
    return_kv: bool = False,  # also return (k, v) as (B, Hkv_loc, S, hd)
):
    b, s_loc, d = x_sp.shape
    pp = _get_attn(p, x_sp.dtype)
    o_rows, kv = _attn_core(cfg, pcfg, info, pp, x_sp,
                            causal=causal, cross_src=cross_src)
    # TP -> SP: GEMM + ReduceScatter
    out = rs_linear(o_rows, pp.wo, pcfg)
    y = x_sp + out.reshape(b, s_loc, d)
    if return_kv:
        return y, kv
    return y


def boundary_mid(r: Array, x_rows: Array, ln: Array, eps: Array) -> Array:
    """The rank-local row-wise seam of the fused attention->MLP boundary:
    residual add + pre-MLP rmsnorm of the reduced attention output.
    Module-level on purpose — the fused op carries ``mid`` as a STATIC,
    so a stable function object keeps trace caches warm. ``eps`` rides
    as a () mid tensor (row-broadcast; its grad is discarded)."""
    return rmsnorm(x_rows + r.astype(x_rows.dtype), ln, eps)


def boundary_fused(pcfg: ParallelConfig) -> bool:
    """Whether the policy turns the attention->MLP seam into the fused
    ``matmul_rs_ag_matmul`` op. Opt-in: the registered default mode is
    "none" (see ``ops.policy.DEFAULT_MODES``), which keeps the composed
    unfused pair — the oracle the equivalence tests pin against."""
    return pcfg.tp > 1 and pcfg.policy.mode_for("matmul_rs_ag_matmul") != "none"


def attn_mlp_train(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    p_attn: dict,
    p_mlp: dict,
    x_sp: Array,  # (B, S_loc, D)
    *,
    causal: bool = True,
) -> Array:
    """One attention + MLP pair with the attention->MLP seam under policy
    control.

    Unfused (the oracle, and the default): ``attention_train`` then
    ``mlp_train`` — three boundary collectives (attention GEMM+RS, MLP
    AG+GEMM, MLP GEMM+RS), the first two fully exposed back to back at
    the seam.

    Fused (when the policy enables ``matmul_rs_ag_matmul``): the seam's
    rs and ag become ONE chained pipeline with the residual+rmsnorm as
    its rank-local ``mid``, and BOTH residual branches close through one
    combined GEMM+RS — ``rs(o @ wo_attn + act(z) @ wo_mlp)`` equals
    ``attn_out + mlp_out``, so the pair runs two boundary crossings
    instead of three. The trade: the attention out-projection GEMM runs
    twice (once inside the fused seam, once in the combined close);
    values match the oracle to f32-accumulation rounding."""
    if not boundary_fused(pcfg):
        h = attention_train(cfg, pcfg, info, p_attn, x_sp, causal=causal)
        return mlp_train(cfg, pcfg, info, p_mlp, h)
    b, s_loc, d = x_sp.shape
    dt = x_sp.dtype
    pp = _get_attn(p_attn, dt)
    o_rows, _ = _attn_core(cfg, pcfg, info, pp, x_sp, causal=causal)
    x_rows = x_sp.reshape(b * s_loc, d)
    ln_mlp = p_mlp["ln"].astype(dt)
    wi, wo_mlp = p_mlp["wi"].astype(dt), p_mlp["wo"].astype(dt)
    eps = jnp.asarray(cfg.norm_eps, jnp.float32)
    z = oplib.matmul_rs_ag_matmul(
        o_rows, pp.wo, wi, x_rows, ln_mlp, eps,
        axis=MODEL_AXIS, policy=pcfg.policy, out_dtype=dt, mid=boundary_mid)
    a = _mlp_act(cfg, z)
    out = rs_linear(jnp.concatenate([o_rows, a], axis=-1),
                    jnp.concatenate([pp.wo, wo_mlp], axis=0), pcfg)
    return x_sp + out.reshape(b, s_loc, d)


def attention_cp(
    pcfg: ParallelConfig,
    q: Array,  # (B, H, S_loc, hd) — sequence-sharded on ``axis``
    k: Array,  # (B, Hkv, S_loc, hd)
    v: Array,  # (B, Hkv, S_loc, hd)
    *,
    axis: str,
    causal: bool = True,
) -> Array:
    """Context-parallel attention: the long-context TRAIN-side attention
    call site. Sequence is sharded on ``axis`` with heads REPLICATED
    there (compose with TP on a different mesh axis — e.g. CP over the
    data axis while projections stay TP-sharded on the model axis); the
    K/V blocks ride the engine transport as ring attention, with the
    transport AND lowering backend resolved by the overlap policy
    (``backend="kernel"`` runs the executor's carry-passing ring_fold
    protocol; grads stay bit-identical across backends)."""
    from ..core.ring_attention import ring_attention

    r = pcfg.policy.resolve("ring_attention")
    return ring_attention(q, k, v, axis, causal=causal, mode=r.mode,
                          backend=r.backend, placement=r.placement,
                          wire=r.wire)


def attention_decode(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    p: dict,
    x: Array,  # (B, 1, D) replicated over tp
    cache_k: Array,  # (B, Hkv_loc, S_max, hd)
    cache_v: Array,
    cache_len: Array,  # scalar OR per-slot (B,) int32
    *,
    cross_kv: Optional[Tuple[Array, Array]] = None,  # precomputed (k, v)
) -> Tuple[Array, Array, Array]:
    b, _, d = x.shape
    hd = cfg.head_dim
    pp = _get_attn(p, x.dtype)
    h = rmsnorm(x, pp.ln, cfg.norm_eps).reshape(b, d)
    q = local_linear(h, pp.wq, pp.bq).reshape(b, info.hq_loc, hd)

    if cross_kv is None:
        kv = local_linear(h, pp.wkv, pp.bkv).reshape(b, 2, info.hkv_loc, hd)
        k_new, v_new = kv[:, 0], kv[:, 1]
        # per-slot write positions (a scalar cache_len broadcasts: the
        # pre-continuous-batching callers advance all slots in lockstep)
        pos = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
        if cfg.use_rope:
            q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
            k_new = rope(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        rows = jnp.arange(b)
        cache_k = cache_k.at[rows, :, pos, :].set(k_new.astype(cache_k.dtype))
        cache_v = cache_v.at[rows, :, pos, :].set(v_new.astype(cache_v.dtype))
        o, _ = ops.flash_decode(q, cache_k, cache_v, pos + 1)
    else:
        ck, cv = cross_kv
        lengths = jnp.full((b,), ck.shape[2], jnp.int32)
        o, _ = ops.flash_decode(q, ck, cv, lengths)

    o = o.astype(x.dtype).reshape(b, info.hq_loc * hd)
    out = psum_tp(local_linear(o, pp.wo), pcfg)  # small AR (low-latency regime)
    return x + out.reshape(b, 1, d), cache_k, cache_v


# ===========================================================================
# Paged attention (block tables over a page pool — serve/kvcache.py)
# ===========================================================================


def _gather_pages(pool: Array, layer: Array, table: Array, hd: int) -> Array:
    """Materialize per-slot KV of one layer from the stacked page pool
    (``ref.gather_pages``): (B, H, P*page_size, hd). Unallocated table
    entries point at scratch page 0; callers mask those positions out by
    length."""
    with obs.scope("paged_gather"):
        return kref.gather_pages(pool, layer, table, hd)


def _write_pages(pool: Array, layer: Array, table: Array, start: Array,
                 n_valid: Array, x: Array) -> Array:
    """Write x (S, C, H, hd), C consecutive tokens of each of S streams,
    into layer ``layer`` of the stacked pool (L, num_pages, H, rows,
    width): token t of stream s lands at position ``start[s] + t`` of the
    pages its block-table row ``table[s]`` lists, for ``t < n_valid[s]``.

    Each page the tokens touch is read whole, the tokens merged in and
    the page written back whole: a scatter indexed by (layer, page) whose
    window is a whole page leaves the pool in its own layout, where one
    indexed by a page's rows would have the compiler lay the pool out
    again around it. Pages with nothing to write go to the layer's
    scratch page 0, unchanged."""
    s, c, h, hd = x.shape
    rows, width = pool.shape[3:]
    parts = width // hd
    ps = rows * parts  # tokens a page
    n = (c + ps - 2) // ps + 1  # pages that c tokens can touch
    seq_page = start[:, None] // ps + jnp.arange(n)  # (S, n) in the sequence
    tok = (seq_page[..., None] * ps + jnp.arange(ps)
           - start[:, None, None])  # (S, n, ps) index into the chunk
    new = (tok >= 0) & (tok < n_valid[:, None, None])
    slot_page = jnp.minimum(seq_page, table.shape[1] - 1)
    page = jnp.where(new.any(-1), jnp.take_along_axis(table, slot_page, 1), 0)
    old = pool[layer, page].reshape(s, n, h, rows, parts, hd)
    put = jnp.take_along_axis(
        x, jnp.clip(tok, 0, c - 1).reshape(s, n * ps, 1, 1), 1)
    put = put.reshape(s, n, rows, parts, h, hd).transpose(0, 1, 4, 2, 3, 5)
    keep = new.reshape(s, n, 1, rows, parts, 1)
    merged = jnp.where(keep, put.astype(pool.dtype), old)
    return pool.at[layer, page].set(merged.reshape(s, n, h, rows, width))


def attention_decode_paged(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    p: dict,
    x: Array,        # (B, 1, D) replicated over tp
    pool_k: Array,   # (L, num_pages, Hkv_loc, rows, width): page_rows
    pool_v: Array,
    layer: Array,    # () int32: this layer's index into the pools
    table: Array,    # (B, P) int32 page ids
    lengths: Array,  # (B,) tokens already cached per slot
    active: Array,   # (B,) bool — idle lanes write to the scratch page
) -> Tuple[Array, Array, Array]:
    """Decode-step attention against layer ``layer`` of the stacked paged
    KV pools: write this token's K/V at each live slot's next position
    (routed through its block table), then flash-decode over each slot's
    pages: read in place by the paged kernel, or gathered whole on the
    reference path."""
    b, _, d = x.shape
    hd = cfg.head_dim
    pp = _get_attn(p, x.dtype)
    with obs.scope("attn"):
        with obs.scope("qkv"):
            h = rmsnorm(x, pp.ln, cfg.norm_eps).reshape(b, d)
            q = local_linear(h, pp.wq, pp.bq).reshape(b, info.hq_loc, hd)
            kv = local_linear(h, pp.wkv, pp.bkv).reshape(b, 2, info.hkv_loc, hd)
            k_new, v_new = kv[:, 0], kv[:, 1]
            pos = lengths.astype(jnp.int32)
            if cfg.use_rope:
                q = rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
                k_new = rope(k_new[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        with obs.scope("kv_write"):
            one = active.astype(jnp.int32)  # idle lanes write nothing
            pool_k = _write_pages(pool_k, layer, table, pos, one,
                                  k_new[:, None])
            pool_v = _write_pages(pool_v, layer, table, pos, one,
                                  v_new[:, None])
        eff = jnp.where(active, pos + 1, 1)
        if ops.use_pallas():
            # the kernel reads each slot's live pages in place
            with obs.scope("flash_decode"):
                o, _ = ops.paged_flash_decode(q, pool_k, pool_v, table, eff,
                                              layer)
        else:
            k_all = _gather_pages(pool_k, layer, table, hd)
            v_all = _gather_pages(pool_v, layer, table, hd)
            with obs.scope("flash_decode"):
                o, _ = ops.flash_decode(q, k_all, v_all, eff)
        with obs.scope("out"):
            o = o.astype(x.dtype).reshape(b, info.hq_loc * hd)
            out = psum_tp(local_linear(o, pp.wo), pcfg)
            return x + out.reshape(b, 1, d), pool_k, pool_v


def _chunk_attend(q: Array, k_all: Array, v_all: Array, qpos: Array,
                  limit: Array) -> Array:
    """Attention of chunk queries at absolute positions ``qpos`` over the
    gathered page pool: key j visible iff j <= qpos_i and j < limit.
    q (B, C, Hq, hd), k_all/v_all (B, Hkv, L, hd) -> (B, C, Hq, hd) f32."""
    b, c, hq, hd = q.shape
    hkv = k_all.shape[1]
    kk = jnp.repeat(k_all, hq // hkv, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v_all, hq // hkv, axis=1).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bchd,bhld->bhcl", q.astype(jnp.float32), kk) * scale
    j = jnp.arange(k_all.shape[2])
    mask = (j[None, :] <= qpos[:, None]) & (j[None, :] < limit)  # (C, L)
    logits = jnp.where(mask[None, None], logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(logits - m)
    w = e / jnp.sum(e, axis=-1, keepdims=True)
    return jnp.einsum("bhcl,bhld->bchd", w, vv)


def attention_prefill_chunk(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    p: dict,
    x_sp: Array,       # (1, C_loc, D) — one request's chunk, SP over tp
    pool_k: Array,     # (L, num_pages, Hkv_loc, rows, width): page_rows
    pool_v: Array,
    layer: Array,      # () int32: this layer's index into the pools
    table_row: Array,  # (P,) int32 — the request's block table
    start: Array,      # scalar int32: absolute position of the chunk's 1st token
    n_valid: Array,    # scalar int32: real tokens in the chunk (rest padding)
) -> Tuple[Array, Array, Array]:
    """One chunked-prefill attention layer: AG+GEMM projections over the
    chunk (resolves ag_matmul), chunk K/V written into layer ``layer`` of
    the stacked paged pools, chunk queries attending over that layer's
    pages (prefix + the chunk itself, causal at absolute positions),
    GEMM+RS back to SP rows (resolves matmul_rs). Padding lanes write to
    the scratch page."""
    b, s_loc, d = x_sp.shape
    tp = pcfg.tp
    c = s_loc * tp
    hd = cfg.head_dim
    pp = _get_attn(p, x_sp.dtype)

    with obs.scope("attn"):
        with obs.scope("qkv"):
            h = rmsnorm(x_sp, pp.ln, cfg.norm_eps).reshape(b * s_loc, d)
            wqkv = jnp.concatenate([pp.wq, pp.wkv], axis=1)
            bqkv = jnp.concatenate([pp.bq, pp.bkv]) if pp.bq is not None else None
            y = ag_linear(h, wqkv, pcfg, bqkv)  # (tp*B*S_loc, cols)
            y = _sp_gathered_to_bsd(y, tp, b, s_loc)  # (1, C, cols)
            q, kv = jnp.split(y, [info.hq_loc * hd], axis=-1)
            k, v = jnp.split(kv, 2, axis=-1)
            q = q.reshape(b, c, info.hq_loc, hd)
            k = k.reshape(b, c, info.hkv_loc, hd)
            v = v.reshape(b, c, info.hkv_loc, hd)
            pos = start + jnp.arange(c)
            if cfg.use_rope:
                q = rope(q, pos, cfg.rope_theta)
                k = rope(k, pos, cfg.rope_theta)

        with obs.scope("kv_write"):
            rows, first, n = table_row[None], start[None], n_valid[None]
            pool_k = _write_pages(pool_k, layer, rows, first, n, k)
            pool_v = _write_pages(pool_v, layer, rows, first, n, v)

        k_all = _gather_pages(pool_k, layer, table_row[None, :], hd)
        v_all = _gather_pages(pool_v, layer, table_row[None, :], hd)
        with obs.scope("chunk_attend"):
            # all-masked rows would NaN; an idle shard (n_valid == 0) attends
            # one scratch position instead, and the caller discards its output
            limit = start + jnp.maximum(n_valid, 1)
            o = _chunk_attend(q, k_all, v_all, pos, limit)
        with obs.scope("out"):
            o = o.astype(x_sp.dtype).reshape(b, c, info.hq_loc * hd)
            out = rs_linear(_bsd_to_sp_rows(o, tp), pp.wo, pcfg)
            return x_sp + out.reshape(b, s_loc, d), pool_k, pool_v


def _prefix_partial(q: Array, k_all: Array, v_all: Array, start: Array):
    """Partial attention of chunk queries over the pool PREFIX [0, start)
    — the positions prefilled by earlier chunks. Returns the online-
    softmax triple (m, l, acc) with acc UN-normalized, for merging with
    the chunk-internal ring partial. ``start == 0`` yields an exact
    no-op partial (m = -1e30, l = 0, acc = 0)."""
    b, c, hq, hd = q.shape
    hkv = k_all.shape[1]
    kk = jnp.repeat(k_all, hq // hkv, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v_all, hq // hkv, axis=1).astype(jnp.float32)
    scale = 1.0 / math.sqrt(hd)
    logits = jnp.einsum("bchd,bhld->bhcl", q.astype(jnp.float32), kk) * scale
    mask = jnp.arange(k_all.shape[2]) < start  # (L,)
    logits = jnp.where(mask[None, None, None], logits, -1e30)
    m = jnp.max(logits, axis=-1)  # (B, Hq, C)
    # p must be masked explicitly: with start == 0 every logit AND m sit
    # at -1e30, so exp(logits - m) would be exp(0) = 1, not 0
    p = jnp.where(mask[None, None, None], jnp.exp(logits - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhcl,bhld->bhcd", p, vv)
    return m, l, acc


def attention_prefill_chunk_cp(
    cfg: ModelConfig,
    pcfg: ParallelConfig,
    info: TPInfo,
    p: dict,
    x_sp: Array,       # (1, C/(cp*tp), D) — this rank's placement rows, SP over tp
    pool_k: Array,     # (L, num_pages, Hkv_loc, rows, width): page_rows
    pool_v: Array,
    layer: Array,      # () int32: this layer's index into the pools
    table_row: Array,  # (P,) int32 — the request's block table
    start: Array,      # scalar int32: absolute position of the chunk's 1st token
    n_valid: Array,    # scalar int32: real tokens in the chunk (rest padding)
    rows_own: Array,   # (C/cp,) int32 — this cp rank's global chunk-row indices
    inv_perm: Array,   # (C,) int32 static — rank-major gather -> position order
    *,
    placement: str,
    cp_attend: str,    # "ring" | "dense"
) -> Tuple[Array, Array, Array]:
    """Context-parallel chunked-prefill attention: ONE request's chunk is
    sharded over the DATA axis by the balanced placement map (each cp
    rank owns C/cp position-ordered rows — zigzag: one early + one late
    half-chunk), with TP projections unchanged within each shard. Chunk
    K/V is all-gathered over the context axis and EVERY rank performs
    the identical scatter-by-table pool write, so the pool replicas stay
    bitwise equal to the dense single-shard path. ``cp_attend="dense"``
    attends each rank's rows over the gathered pages (bit-exact vs
    :func:`attention_prefill_chunk`); ``"ring"`` runs the chunk-internal
    part through the balanced ring_attention op (placement-aware causal
    fold, policy-resolved transport/backend) and merges the pool-prefix
    partial by online softmax."""
    from ..core.ring_attention import ring_attention

    b, s_loc, d = x_sp.shape
    tp = pcfg.tp
    c_own = s_loc * tp  # this cp rank's chunk rows
    hd = cfg.head_dim
    pp = _get_attn(p, x_sp.dtype)

    h = rmsnorm(x_sp, pp.ln, cfg.norm_eps).reshape(b * s_loc, d)
    wqkv = jnp.concatenate([pp.wq, pp.wkv], axis=1)
    bqkv = jnp.concatenate([pp.bq, pp.bkv]) if pp.bq is not None else None
    y = ag_linear(h, wqkv, pcfg, bqkv)
    y = _sp_gathered_to_bsd(y, tp, b, s_loc)  # (1, C_own, cols)
    q, kv = jnp.split(y, [info.hq_loc * hd], axis=-1)
    k, v = jnp.split(kv, 2, axis=-1)
    q = q.reshape(b, c_own, info.hq_loc, hd)
    k = k.reshape(b, c_own, info.hkv_loc, hd)
    v = v.reshape(b, c_own, info.hkv_loc, hd)
    pos_own = start + rows_own
    if cfg.use_rope:
        q = rope(q, pos_own, cfg.rope_theta)
        k = rope(k, pos_own, cfg.rope_theta)

    # every cp rank reconstructs the FULL chunk K/V in position order and
    # performs the identical pool write — replicas stay bitwise equal
    k_ord = lax.all_gather(k[0], DATA_AXIS, axis=0, tiled=True)[inv_perm]
    v_ord = lax.all_gather(v[0], DATA_AXIS, axis=0, tiled=True)[inv_perm]
    rows, first, n = table_row[None], start[None], n_valid[None]
    pool_k = _write_pages(pool_k, layer, rows, first, n, k_ord[None])
    pool_v = _write_pages(pool_v, layer, rows, first, n, v_ord[None])

    k_all = _gather_pages(pool_k, layer, table_row[None, :], hd)
    v_all = _gather_pages(pool_v, layer, table_row[None, :], hd)
    limit = start + jnp.maximum(n_valid, 1)
    if cp_attend == "dense":
        o = _chunk_attend(q, k_all, v_all, pos_own, limit)
    else:  # "ring"
        r = pcfg.policy.resolve("ring_attention")
        stats = ring_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), DATA_AXIS, causal=True, mode=r.mode,
            backend=r.backend, placement=placement, wire=r.wire,
            with_stats=True)  # (1, Hq_loc, C_own, hd + 2)
        out_c = stats[..., :hd]
        m_c, l_c = stats[..., hd], stats[..., hd + 1]
        m_p, l_p, acc_p = _prefix_partial(q, k_all, v_all, start)
        mm = jnp.maximum(m_c, m_p)
        a_p = jnp.exp(m_p - mm)
        a_c = jnp.exp(m_c - mm) * l_c  # chunk acc = out_c * l_c
        num = a_p[..., None] * acc_p + a_c[..., None] * out_c
        den = a_p * l_p + a_c  # >= l_c > 0: causal self term always present
        o = (num / den[..., None]).transpose(0, 2, 1, 3)  # (1, C_own, Hq, hd)
    o = o.astype(x_sp.dtype).reshape(b, c_own, info.hq_loc * hd)
    out = rs_linear(_bsd_to_sp_rows(o, tp), pp.wo, pcfg)
    return x_sp + out.reshape(b, s_loc, d), pool_k, pool_v


# ===========================================================================
# MLP
# ===========================================================================


def mlp_specs(cfg: ModelConfig, info: TPInfo) -> Dict[str, LeafSpec]:
    d = cfg.d_model
    n_up = 2 if cfg.gated_mlp else 1
    return {
        "ln": LeafSpec((d,), tp_sharded=False, init="ones"),
        "wi": LeafSpec((d, n_up * info.dff_loc), fan_in=d),
        "wo": LeafSpec((info.dff_loc, d), fan_in=info.dff_loc * info.tp),
    }


def _mlp_act(cfg, y: Array) -> Array:
    act = activation(cfg.activation)
    if cfg.gated_mlp:
        gate, up = jnp.split(y, 2, axis=-1)
        return act(gate.astype(jnp.float32)).astype(y.dtype) * up
    return act(y.astype(jnp.float32)).astype(y.dtype)


def mlp_train(cfg, pcfg, info, p: dict, x_sp: Array) -> Array:
    with obs.scope("mlp"):
        b, s_loc, d = x_sp.shape
        dt = x_sp.dtype
        h = rmsnorm(x_sp, p["ln"].astype(dt), cfg.norm_eps).reshape(b * s_loc, d)
        y = ag_linear(h, p["wi"].astype(dt), pcfg)  # (tp*B*S_loc, n_up*dff_loc)
        y = _mlp_act(cfg, y)
        out = rs_linear(y, p["wo"].astype(dt), pcfg)  # rows already rank-major
        return x_sp + out.reshape(b, s_loc, d)


def mlp_decode(cfg, pcfg, info, p: dict, x: Array) -> Array:
    with obs.scope("mlp"):
        b, t, d = x.shape
        dt = x.dtype
        h = rmsnorm(x, p["ln"].astype(dt), cfg.norm_eps).reshape(b * t, d)
        y = _mlp_act(cfg, local_linear(h, p["wi"].astype(dt)))
        out = psum_tp(local_linear(y, p["wo"].astype(dt)), pcfg)
        return x + out.reshape(b, t, d)


# ===========================================================================
# MoE (TP mode: paper's AG+MoE / MoE+RS; EP mode: AllToAll dispatch/combine)
# ===========================================================================


def moe_specs(cfg: ModelConfig, info: TPInfo) -> Dict[str, LeafSpec]:
    d = cfg.d_model
    e = cfg.num_experts
    if info.moe_mode == "ep":
        e_loc, dff = info.e_loc, cfg.d_ff
    else:
        e_loc, dff = e, info.dff_loc
    n_up = 2 if cfg.gated_mlp else 1
    return {
        "ln": LeafSpec((d,), tp_sharded=False, init="ones"),
        "router": LeafSpec((d, e), tp_sharded=False, fan_in=d),
        "wi": LeafSpec((e_loc, d, n_up * dff), fan_in=d),
        "wo": LeafSpec(
            (e_loc, dff, d), fan_in=dff * (1 if info.moe_mode == "ep" else info.tp)
        ),
    }


def _expert_ffn(cfg, x_disp: Array, wi: Array, wo: Array) -> Array:
    """(E, cap, D) -> (E, cap, D) through the expert MLPs (grouped GEMMs)."""
    y = ops.grouped_matmul(x_disp, wi, out_dtype=x_disp.dtype)
    y = _mlp_act(cfg, y)
    return ops.grouped_matmul(y, wo, out_dtype=x_disp.dtype)


def _capacity(t: int, k: int, e: int, factor: Optional[float]) -> int:
    """Rows of each expert's buffer for ``t`` tokens: ``factor`` times an
    even share of the t*k routed rows, or, with no factor, t: a token
    picks k distinct experts, so no buffer can overflow (dropless)."""
    if factor is None:
        return t
    cap = int(math.ceil(t * k / e * factor))
    return max(8, ((cap + 7) // 8) * 8)


def _route_dispatch(h: Array, logits: Array, k: int, cap: int):
    """Scopes ``router`` (softmax, top-k) and ``dispatch`` (the scatter
    into the (E, cap, d) buffers)."""
    with obs.scope("router"):
        top_e, top_p = mo.topk_route(logits, k)
    with obs.scope("dispatch"):
        return mo.dispatch(h, top_e, top_p, logits.shape[-1], cap)


def _router_logits(cfg, p: dict, x: Array, dt) -> Tuple[Array, Array]:
    """(normed tokens (T, d), router logits (T, E)) under ``router``."""
    with obs.scope("router"):
        h = rmsnorm(x, p["ln"].astype(dt), cfg.norm_eps).reshape(-1, x.shape[-1])
        return h, local_linear(h, p["router"].astype(dt))


def _local_experts(cfg, h, logits, wi, wo, k: int, cap: int, dt) -> Array:
    """Every expert on this rank, no exchange: route, dispatch, the
    grouped GEMMs (``experts``), and the weighted gather back
    (``combine``)."""
    disp, dinfo = _route_dispatch(h, logits, k, cap)
    with obs.scope("experts"):
        y = _expert_ffn(cfg, disp, wi, wo)
    with obs.scope("combine"):
        return mo.topk_combine(y, dinfo, out_dtype=dt)


def _ep_experts(cfg, pcfg, h, logits, wi, wo, k: int, cap: int, dt) -> Array:
    """Experts sharded over the model axis: the AllToAll out rides in
    ``dispatch``, the one back in ``combine``."""
    a2a = pcfg.policy.resolve("a2a_ep")
    disp, dinfo = _route_dispatch(h, logits, k, cap)
    with obs.scope("dispatch"):
        x_ep = mo.a2a_ep(disp, MODEL_AXIS, mode=a2a.mode,
                         backend=a2a.backend, wire=a2a.wire)
    with obs.scope("experts"):
        y_ep = _expert_ffn(cfg, x_ep, wi, wo)  # (E_loc, tp*cap, D)
    with obs.scope("combine"):
        back = mo.a2a_ep_inverse(y_ep, MODEL_AXIS, mode=a2a.mode,
                                 backend=a2a.backend, wire=a2a.wire)
        return mo.topk_combine(back, dinfo, out_dtype=dt)


def moe_train(cfg, pcfg, info, p: dict, x_sp: Array,
              factor: Optional[float]) -> Array:
    """The routed-expert block over a sequence. Each expert's buffer is
    sized by ``_capacity`` from the tokens dispatched together and
    ``factor``: training passes ``cfg.capacity_factor``, the serving
    prefill programs None (dropless)."""
    with obs.scope("moe"):
        b, s_loc, d = x_sp.shape
        tp = pcfg.tp
        dt = x_sp.dtype
        wi, wo = p["wi"].astype(dt), p["wo"].astype(dt)
        h, logits = _router_logits(cfg, p, x_sp, dt)  # (T_loc, d), (T_loc, E)
        k = cfg.experts_per_token

        if info.moe_mode == "ep" and tp > 1:
            # token chunking bounds the (E, cap, d) dispatch buffers AND is the
            # natural grain for overlapping a2a(chunk i+1) with experts(chunk i)
            t_loc = h.shape[0]
            n_chunks = max(1, min(pcfg.moe_chunks, t_loc))
            while t_loc % n_chunks != 0:
                n_chunks -= 1
            t_c = t_loc // n_chunks
            cap = _capacity(t_c, k, cfg.num_experts, factor)

            def ep_chunk(hc, lc):
                return _ep_experts(cfg, pcfg, hc, lc, wi, wo, k, cap, dt)

            if pcfg.remat != "none":
                ep_chunk = jax.checkpoint(ep_chunk)
            outs = []
            for ci in range(n_chunks):
                hc = lax.dynamic_slice(h, (ci * t_c, 0), (t_c, d))
                lc = lax.dynamic_slice(logits, (ci * t_c, 0), (t_c, logits.shape[1]))
                outs.append(ep_chunk(hc, lc))
            out = jnp.concatenate(outs, axis=0) if n_chunks > 1 else outs[0]
            return x_sp + out.reshape(b, s_loc, d)

        # TP mode: AllGather token chunks around the ring, run the d_ff-sharded
        # experts per chunk (AG+MoE), then ring-ReduceScatter the partial
        # outputs (MoE+RS). (EP configs on tp=1 meshes also land here.)
        def expert_fn(tokens, tok_logits):
            # the tokens this call is handed: a ring chunk, or every
            # rank's when the gather is monolithic
            cap = _capacity(tokens.shape[0], k, cfg.num_experts, factor)
            return _local_experts(cfg, tokens, tok_logits, wi, wo, k, cap,
                                  tokens.dtype)

        if pcfg.remat != "none":
            # per-ring-chunk checkpoint: the backward live-set is one chunk's
            # dispatch buffers, not all W chunks' (the ring makes W of them)
            expert_fn = jax.checkpoint(expert_fn)

        if tp > 1:
            # ag_moe carries a derived vjp-of-closure backward (the kernel
            # forward keeps the graph-schedule dual through the ONE shared
            # custom_vjp), so the TRAIN path follows the policy's backend —
            # the graph-only pin is gone.
            ag = pcfg.policy.resolve("ag_moe")
            full = mo.ag_moe(h, logits, expert_fn, MODEL_AXIS,
                             mode=ag.mode, backend=ag.backend)
            rs = pcfg.policy.resolve("reduce_scatter")
            with obs.scope("combine"):
                out = cm.reduce_scatter_chunked(full, MODEL_AXIS, mode=rs.mode,
                                                backend=rs.backend, wire=rs.wire)
        else:
            out = expert_fn(h, logits)
        return x_sp + out.reshape(b, s_loc, d)


def moe_decode(cfg, pcfg, info, p: dict, x: Array) -> Array:
    """The routed-expert block for decode tokens, dropless: each expert's
    buffer holds every token of the call (serving only)."""
    with obs.scope("moe"):
        b, t, d = x.shape
        dt = x.dtype
        wi, wo = p["wi"].astype(dt), p["wo"].astype(dt)
        h, logits = _router_logits(cfg, p, x, dt)
        k = cfg.experts_per_token
        cap = _capacity(h.shape[0], k, cfg.num_experts, None)
        if info.moe_mode == "ep" and pcfg.tp > 1:
            out = _ep_experts(cfg, pcfg, h, logits, wi, wo, k, cap, dt)
        else:
            out = _local_experts(cfg, h, logits, wi, wo, k, cap, dt)
            if info.moe_mode == "tp":
                with obs.scope("combine"):
                    out = psum_tp(out, pcfg)
        return x + out.reshape(b, t, d)


# ===========================================================================
# Mamba2 (SSD) block
# ===========================================================================


def ssm_specs(cfg: ModelConfig, info: TPInfo) -> Dict[str, LeafSpec]:
    d = cfg.d_model
    gs = cfg.ssm_num_groups * cfg.ssm_state
    cols = 2 * info.di_loc + 2 * gs + info.nh_loc  # z | x | B | C | dt
    conv_ch = info.di_loc + 2 * gs
    return {
        "ln": LeafSpec((d,), tp_sharded=False, init="ones"),
        "w_in": LeafSpec((d, cols), fan_in=d),
        "conv": LeafSpec(
            (cfg.ssm_conv_width, conv_ch), init="normal", fan_in=cfg.ssm_conv_width
        ),
        "a_log": LeafSpec((info.nh_loc,), init="ssm_a"),
        "dt_bias": LeafSpec((info.nh_loc,), init="ssm_dt"),
        "d_skip": LeafSpec((info.nh_loc,), init="ones"),
        "w_out": LeafSpec((info.di_loc, d), fan_in=info.di_loc * info.tp),
    }


def _causal_depthwise_conv(x: Array, w: Array) -> Array:
    """x: (B, S, C), w: (width, C) — causal depthwise conv + silu."""
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros(x.shape, jnp.float32)
    for i in range(width):
        out = out + pad[:, i : i + x.shape[1], :].astype(jnp.float32) * w[i].astype(
            jnp.float32
        )[None, None, :]
    return jax.nn.silu(out).astype(x.dtype)


def _split_ssm_proj(y: Array, cfg, info):
    gs = cfg.ssm_num_groups * cfg.ssm_state
    di = info.di_loc
    z, xs, bmat, cmat, dt = jnp.split(
        y, [di, 2 * di, 2 * di + gs, 2 * di + 2 * gs], axis=-1
    )
    return z, xs, bmat, cmat, dt


def ssm_train(cfg, pcfg, info, p: dict, x_sp: Array) -> Array:
    b, s_loc, d = x_sp.shape
    tp = pcfg.tp
    s = s_loc * tp
    dt_ = x_sp.dtype
    a_log = p["a_log"].astype(jnp.float32)
    dt_bias = p["dt_bias"].astype(jnp.float32)
    d_skip = p["d_skip"].astype(jnp.float32)

    h = rmsnorm(x_sp, p["ln"].astype(dt_), cfg.norm_eps).reshape(b * s_loc, d)
    y = ag_linear(h, p["w_in"].astype(dt_), pcfg)  # SP->TP overlapped projection
    y = _sp_gathered_to_bsd(y, tp, b, s_loc)  # (B, S, cols)
    z, xs, bmat, cmat, dtp = _split_ssm_proj(y, cfg, info)

    conv_in = jnp.concatenate([xs, bmat, cmat], axis=-1)
    conv_out = _causal_depthwise_conv(conv_in, p["conv"])
    xs, bmat, cmat = jnp.split(
        conv_out,
        [info.di_loc, info.di_loc + cfg.ssm_num_groups * cfg.ssm_state],
        axis=-1,
    )

    nh, hp = info.nh_loc, cfg.ssm_head_dim
    xh = xs.reshape(b, s, nh, hp)
    dtv = jax.nn.softplus(dtp.astype(jnp.float32) + dt_bias)  # (B, S, nh)
    a = -jnp.exp(a_log)  # (nh,)
    bm = bmat.reshape(b, s, cfg.ssm_num_groups, cfg.ssm_state)
    cmx = cmat.reshape(b, s, cfg.ssm_num_groups, cfg.ssm_state)
    yh, _ = ops.ssd_scan(xh, dtv, a, bm, cmx)
    yh = yh.astype(jnp.float32) + d_skip[None, None, :, None] * xh.astype(jnp.float32)
    y = (yh.reshape(b, s, nh * hp) * jax.nn.silu(z.astype(jnp.float32))).astype(dt_)
    out = rs_linear(_bsd_to_sp_rows(y, tp), p["w_out"].astype(dt_), pcfg)  # TP->SP
    return x_sp + out.reshape(b, s_loc, d)


def ssm_decode(
    cfg, pcfg, info, p: dict, x: Array, conv_state: Array, ssd_state: Array
) -> Tuple[Array, Array, Array]:
    """x: (B, 1, D); conv_state: (B, width-1, conv_ch);
    ssd_state: (B, nh_loc, P, S) f32."""
    b, _, d = x.shape
    dt_ = x.dtype
    a_log = p["a_log"].astype(jnp.float32)
    dt_bias = p["dt_bias"].astype(jnp.float32)
    d_skip = p["d_skip"].astype(jnp.float32)

    h = rmsnorm(x, p["ln"].astype(dt_), cfg.norm_eps).reshape(b, d)
    y = local_linear(h, p["w_in"].astype(dt_))
    z, xs, bmat, cmat, dtp = _split_ssm_proj(y, cfg, info)

    conv_in = jnp.concatenate([xs, bmat, cmat], axis=-1)  # (B, conv_ch)
    window = jnp.concatenate([conv_state, conv_in[:, None, :]], axis=1)  # (B, w, C)
    conv_out = jax.nn.silu(
        jnp.sum(window.astype(jnp.float32) * p["conv"].astype(jnp.float32)[None], axis=1)
    ).astype(dt_)
    new_conv_state = window[:, 1:, :]
    xs, bmat, cmat = jnp.split(
        conv_out,
        [info.di_loc, info.di_loc + cfg.ssm_num_groups * cfg.ssm_state],
        axis=-1,
    )

    nh, hp = info.nh_loc, cfg.ssm_head_dim
    xh = xs.reshape(b, nh, hp).astype(jnp.float32)
    dtv = jax.nn.softplus(dtp.astype(jnp.float32) + dt_bias)  # (B, nh)
    a = -jnp.exp(a_log)
    rep = nh // cfg.ssm_num_groups if nh >= cfg.ssm_num_groups else 1
    bm = jnp.repeat(
        bmat.reshape(b, cfg.ssm_num_groups, cfg.ssm_state), rep, axis=1
    ).astype(jnp.float32)
    cmx = jnp.repeat(
        cmat.reshape(b, cfg.ssm_num_groups, cfg.ssm_state), rep, axis=1
    ).astype(jnp.float32)
    decay = jnp.exp(dtv * a[None, :])  # (B, nh)
    new_state = ssd_state * decay[..., None, None] + (
        xh[..., :, None] * bm[..., None, :]
    ) * dtv[..., None, None]
    yh = jnp.einsum("bhps,bhs->bhp", new_state, cmx) + d_skip[None, :, None] * xh
    yv = (yh.reshape(b, nh * hp) * jax.nn.silu(z.astype(jnp.float32))).astype(dt_)
    out = psum_tp(local_linear(yv, p["w_out"].astype(dt_)), pcfg)
    return x + out.reshape(b, 1, d), new_conv_state, new_state
