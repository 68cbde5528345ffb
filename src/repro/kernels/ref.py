"""Pure-jnp oracles for every Pallas kernel (the ref side of the
kernel-vs-ref allclose sweeps). No Pallas, no collectives — just math."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def matmul(a: jax.Array, b: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def grouped_matmul(x: jax.Array, w: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """x: (E, cap, d_in), w: (E, d_in, d_out) -> (E, cap, d_out).

    No operand casts: bf16 inputs feed the dot directly with f32
    accumulation (an .astype(f32) here would materialize an f32 copy of
    every expert weight — gigabytes for large MoEs)."""
    return jnp.einsum(
        "ecd,edf->ecf", x, w, preferred_element_type=jnp.float32
    ).astype(out_dtype)


def flash_attention(
    q: jax.Array,  # (B, Hq, Lq, D)
    k: jax.Array,  # (B, Hkv, Lk, D)
    v: jax.Array,  # (B, Hkv, Lk, D)
    *,
    causal: bool = True,
    scale: float | None = None,
) -> jax.Array:
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kk.astype(jnp.float32)) * scale
    if causal:
        lk = k.shape[2]
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vv.astype(jnp.float32)).astype(q.dtype)


def flash_attention_chunked(
    q: jax.Array,  # (B, Hq, Lq, D)
    k: jax.Array,  # (B, Hkv, Lk, D)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: float | None = None,
    kv_chunk: int = 512,
) -> jax.Array:
    """Online-softmax attention scanned over KV chunks — O(Lq * chunk)
    live memory instead of O(Lq * Lk). The production XLA path for long
    sequences (the Pallas kernel is the TPU fast path)."""
    b, hq, lq, d = q.shape
    hkv, lk = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kv_chunk = min(kv_chunk, lk)
    if lk % kv_chunk != 0:
        return flash_attention(q, k, v, causal=causal, scale=scale)
    n_chunks = lk // kv_chunk

    qf = q.astype(jnp.float32) * scale
    ks = jnp.moveaxis(k.reshape(b, hkv, n_chunks, kv_chunk, d), 2, 0)
    vs = jnp.moveaxis(v.reshape(b, hkv, n_chunks, kv_chunk, d), 2, 0)
    rows = jnp.arange(lq)[:, None]

    @jax.checkpoint  # don't save per-chunk probability residuals — the
    def step(carry, inp):  # backward recomputes each chunk from (q, kc, vc)
        m, l, acc = carry
        idx, kc, vc = inp  # kc: (B, Hkv, C, D)
        kcr = jnp.repeat(kc.astype(jnp.float32), group, axis=1)
        vcr = jnp.repeat(vc.astype(jnp.float32), group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kcr)
        if causal:
            cols = idx * kv_chunk + jnp.arange(kv_chunk)[None, :]
            s = jnp.where(rows + (lk - lq) >= cols, s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vcr)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, lq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hq, lq), jnp.float32)
    a0 = jnp.zeros((b, hq, lq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (jnp.arange(n_chunks), ks, vs))
    return (acc / l[..., None]).astype(q.dtype)


def flash_decode(
    q: jax.Array,  # (B, Hq, D) — one new token
    k: jax.Array,  # (B, Hkv, S, D)
    v: jax.Array,  # (B, Hkv, S, D)
    *,
    scale: float | None = None,
    length: jax.Array | None = None,  # (B,) valid KV length per sequence
):
    """Returns (o, lse): o (B, Hq, D) fp32, lse (B, Hq) fp32.

    lse is the log-sum-exp of the attention logits — the quantity the
    distributed flash-decode combine needs to merge partial results from
    KV shards (paper §4.2 FlashDecode+AG).
    """
    b, hq, d = q.shape
    hkv, s = k.shape[1], k.shape[2]
    group = hq // hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    kk = jnp.repeat(k, group, axis=1)
    vv = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32), kk.astype(jnp.float32)) * scale
    if length is not None:
        mask = jnp.arange(s)[None, None, :] < length[:, None, None]
        logits = jnp.where(mask, logits, -1e30)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhs,bhsd->bhd", p / l, vv.astype(jnp.float32))
    lse = (m + jnp.log(l))[..., 0]
    return o, lse


def gather_pages(pool: jax.Array, layer: jax.Array, table: jax.Array,
                 head_dim: int) -> jax.Array:
    """Per-slot K/V of one layer copied out of pools stacked over layers:
    pool (L, num_pages, H, rows, width), each head's page its tokens in
    order (kernels.flash_decode.page_rows), read at ``layer`` through
    table (B, P) page ids -> (B, H, P * page_size, head_dim)."""
    _, _, h, rows, width = pool.shape
    b, pcount = table.shape
    ps = rows * width // head_dim
    g = pool[layer, table].reshape(b, pcount, h, ps, head_dim)
    return g.transpose(0, 2, 1, 3, 4).reshape(b, h, pcount * ps, head_dim)


def paged_flash_decode(q, pool_k, pool_v, table, length, layer, *,
                       scale=None):
    """flash_decode over each slot's pages of one layer, gathered whole
    (entries past the length are masked out by it)."""
    d = q.shape[-1]
    return flash_decode(q, gather_pages(pool_k, layer, table, d),
                        gather_pages(pool_v, layer, table, d), scale=scale,
                        length=length)


def combine_flash_decode(o_parts: jax.Array, lse_parts: jax.Array) -> jax.Array:
    """Merge per-shard partial attention results.

    o_parts: (W, B, H, D) fp32; lse_parts: (W, B, H) fp32 -> (B, H, D).
    """
    m = jnp.max(lse_parts, axis=0, keepdims=True)
    w = jnp.exp(lse_parts - m)  # (W, B, H)
    num = jnp.sum(o_parts * w[..., None], axis=0)
    den = jnp.sum(w, axis=0)
    return num / den[..., None]


def ssd_scan(
    x: jax.Array,  # (B, L, H, P)
    dt: jax.Array,  # (B, L, H) — positive step sizes
    a: jax.Array,  # (H,) — negative decay rates (A_log already exp'ed * -1)
    b_mat: jax.Array,  # (B, L, G, S)
    c_mat: jax.Array,  # (B, L, G, S)
    *,
    init_state: jax.Array | None = None,  # (B, H, P, S)
):
    """Sequential reference for the Mamba2 SSD recurrence.

    S_t = exp(dt_t * a) * S_{t-1} + dt_t * (x_t outer B_t)
    y_t = S_t . C_t
    Returns (y, final_state): y (B, L, H, P), state (B, H, P, S).
    """
    bsz, seqlen, h, p = x.shape
    g, s = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = jnp.repeat(b_mat.astype(jnp.float32), rep, axis=2)  # (B, L, H, S)
    cf = jnp.repeat(c_mat.astype(jnp.float32), rep, axis=2)

    def step(state, inp):
        xt, dtt, bt, ct = inp  # (B,H,P), (B,H), (B,H,S), (B,H,S)
        decay = jnp.exp(dtt * a[None, :])  # (B,H)
        state = state * decay[..., None, None] + (
            xt[..., :, None] * bt[..., None, :]
        ) * dtt[..., None, None]
        y = jnp.einsum("bhps,bhs->bhp", state, ct)
        return state, y

    state0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((bsz, h, p, s), jnp.float32)
    )
    inps = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(bf, 1, 0),
        jnp.moveaxis(cf, 1, 0),
    )
    final, ys = jax.lax.scan(step, state0, inps)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def ssd_scan_chunked(
    x: jax.Array,  # (B, L, H, P)
    dt: jax.Array,  # (B, L, H)
    a: jax.Array,  # (H,)
    b_mat: jax.Array,  # (B, L, G, S)
    c_mat: jax.Array,  # (B, L, G, S)
    *,
    chunk: int = 128,
    init_state: jax.Array | None = None,
):
    """Chunked SSD (same closed form as the Pallas kernel) in pure jnp —
    the production XLA path. The per-timestep reference scan is O(L) deep:
    its backward saves a state residual per TIME STEP (gigabytes at 4k
    context). This version scans per CHUNK with a checkpointed body, so the
    backward saves one state per chunk and recomputes inside.
    """
    bsz, seqlen, h, p = x.shape
    g, s = b_mat.shape[2], b_mat.shape[3]
    rep = h // g
    chunk = min(chunk, seqlen)
    if seqlen % chunk != 0:
        return ssd_scan(x, dt, a, b_mat, c_mat, init_state=init_state)
    nc = seqlen // chunk

    xf = x.reshape(bsz, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    bf = jnp.repeat(b_mat, rep, axis=2).reshape(bsz, nc, chunk, h, s)
    cf = jnp.repeat(c_mat, rep, axis=2).reshape(bsz, nc, chunk, h, s)

    log_decay = dtf * a[None, None, None, :]  # (B, NC, C, H)
    cum = jnp.cumsum(log_decay, axis=2)  # inclusive L_t

    rows = jnp.arange(chunk)[:, None]
    cols = jnp.arange(chunk)[None, :]
    mask = rows >= cols

    @jax.checkpoint
    def body(state, inp):
        xc, dtc, bc, cc, cumc = inp  # (B, C, H, *)
        xc = xc.astype(jnp.float32)
        # intra-chunk masked matmul: G[t,u] = (c_t.b_u) exp(L_t - L_u) dt_u
        cb = jnp.einsum("bths,buhs->bhtu", cc, bc,
                        preferred_element_type=jnp.float32)
        decay = jnp.exp(
            cumc.transpose(0, 2, 1)[:, :, :, None]
            - cumc.transpose(0, 2, 1)[:, :, None, :]
        )  # (B, H, C, C)
        gate = jnp.where(mask[None, None], cb * decay, 0.0) * \
            dtc.transpose(0, 2, 1)[:, :, None, :]  # * dt_u
        y = jnp.einsum("bhtu,buhp->bthp", gate, xc)
        # inter-chunk from the carried state
        y = y + jnp.exp(cumc)[..., None] * jnp.einsum(
            "bths,bhps->bthp", cc, state, preferred_element_type=jnp.float32)
        # state update: S <- exp(L_C) S + sum_u exp(L_C - L_u) dt_u x_u (x) B_u
        w = jnp.exp(cumc[:, -1:, :] - cumc) * dtc  # (B, C, H)
        new_state = jnp.exp(cumc[:, -1])[..., None, None] * state + jnp.einsum(
            "bthp,bths->bhps", xc, bc * w[..., None],
            preferred_element_type=jnp.float32)
        return new_state, y

    state0 = (
        init_state.astype(jnp.float32)
        if init_state is not None
        else jnp.zeros((bsz, h, p, s), jnp.float32)
    )
    xs = (
        jnp.moveaxis(xf, 1, 0),
        jnp.moveaxis(dtf, 1, 0),
        jnp.moveaxis(bf.astype(jnp.float32), 1, 0),
        jnp.moveaxis(cf.astype(jnp.float32), 1, 0),
        jnp.moveaxis(cum, 1, 0),
    )
    final, ys = jax.lax.scan(body, state0, xs)  # ys: (NC, B, C, H, P)
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, seqlen, h, p)
    return y.astype(x.dtype), final


def ag_gemm(a_shards: jax.Array, b_loc: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """Oracle for the fused AllGather-GEMM kernel, from a global view:
    a_shards (W, m_loc, K) stacked shards, b_loc (K, n_loc) one rank's B."""
    a_full = a_shards.reshape(-1, a_shards.shape[-1])
    return matmul(a_full, b_loc, out_dtype)


def all_gather(a_shards: jax.Array) -> jax.Array:
    """Oracle for the low-latency AllGather kernel: (W, m, ...) -> concat."""
    return a_shards.reshape((-1,) + a_shards.shape[2:])
