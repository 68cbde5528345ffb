"""Plain float32 forward pass of the configurations' architecture.

A pre-norm decoder: RMSNorm, grouped-query attention with rotary
positions (the rotate-half form, base ``rope_theta``), causal softmax
scaled by 1/sqrt(head_dim); then RMSNorm and a SiLU-gated MLP, or for a
mixture of experts a softmax router whose top ``num_experts_per_tok``
probabilities, renormalised to sum to 1, weight the outputs of those
experts' gated MLPs, with no token dropped; a final RMSNorm and the tied
embedding as the output projection. Every matrix product runs at
``highest`` precision, so float32 on a TPU is float32.

It is written from the equations, imports nothing of the program, and
runs one layer at a time over one sequence, so that it fits on the chip
after the program's state is freed. ``quant="fp8"`` is the control: the
same pass with both operands of every projection rounded to float8
(e4m3, one scale per tensor), the step below the bf16 the
configurations serve in.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512  # query rows per attention block
SEQ_BUCKET = 1024  # sequences are padded to a multiple: few shapes to compile


def _fp8(x):
    """x rounded to float8 e4m3 with one scale for the whole tensor."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, quant: Optional[str]):
    if quant == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a.astype(F32), b.astype(F32), precision=HI)


def rms_norm(x, scale, eps):
    x = x.astype(F32)
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * \
        scale.astype(F32)


def rotary(x, pos, theta):
    """x (S, H, hd) at positions pos (S,): the rotate-half form."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, spec, quant):
    """Causal grouped-query self-attention over the whole sequence."""
    s = x.shape[0]
    hd = spec["head_dim"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    pos = jnp.arange(s)
    q = rotary(_mm(x, w["wq"], quant).reshape(s, hq, hd), pos,
               spec["rope_theta"])
    k = rotary(_mm(x, w["wk"], quant).reshape(s, hkv, hd), pos,
               spec["rope_theta"])
    v = _mm(x, w["wv"], quant).reshape(s, hkv, hd)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    out = []
    for lo in range(0, s, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / math.sqrt(hd)
        visible = pos[None, :] <= pos[lo:lo + Q_BLOCK, None]
        sc = jnp.where(visible[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    o = jnp.concatenate(out, axis=0).reshape(s, hq * hd)
    return _mm(o, w["wo"], quant)


def gated_mlp(x, w_gate, w_up, w_down, quant):
    return _mm(jax.nn.silu(_mm(x, w_gate, quant)) * _mm(x, w_up, quant),
               w_down, quant)


def moe(x, w, spec, quant):
    """Top-k routed experts, dropless: every expert's output is computed
    for every token and weighted by its renormalised gate, which is 0
    for the experts a token does not pick. Tokens go in blocks of
    Q_BLOCK, all experts at once."""
    k = spec["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(x, w["router"], quant), axis=-1)  # (S, E)
    top_p, top_e = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], top_e].set(top_p)
    out = []
    for lo in range(0, x.shape[0], Q_BLOCK):
        xb = x[lo:lo + Q_BLOCK]
        y = gated_mlp(xb[None], w["w_gate"], w["w_up"], w["w_down"],
                      quant)  # (E, block, D)
        out.append(jnp.einsum("se,esd->sd", gates[lo:lo + Q_BLOCK], y,
                              precision=HI))
    return jnp.concatenate(out, axis=0)


def layer(h, w, spec, quant):
    eps = spec["rms_norm_eps"]
    h = h + attention(rms_norm(h, w["attn_norm"], eps), w, spec, quant)
    x = rms_norm(h, w["mlp_norm"], eps)
    if spec.get("num_local_experts", 0):
        return h + moe(x, w, spec, quant)
    return h + gated_mlp(x, w["w_gate"], w["w_up"], w["w_down"], quant)


@functools.lru_cache(maxsize=None)
def _jitted(spec_items, quant):
    spec = dict(spec_items)
    return (jax.jit(lambda h, w: layer(h, w, spec, quant)),
            jax.jit(lambda h, top, rows: _mm(
                rms_norm(h[rows], top["final_norm"], spec["rms_norm_eps"]),
                top["embed"].T, quant)))


def logits(tokens: np.ndarray, rows: np.ndarray, spec: dict,
           layer_weights: Callable[[int], Dict], top: Dict,
           quant: Optional[str] = None) -> jax.Array:
    """Next-token logits (len(rows), vocab) at the positions ``rows`` of
    the sequence ``tokens``, in float32, on the device. ``layer_weights(i)`` gives layer
    i's weights (as ``bench/model.py`` unpacks them), ``top`` the
    embedding and the final norm. The sequence and the rows are padded
    to a multiple of SEQ_BUCKET (a causal mask keeps earlier positions
    as they are), so that few shapes compile."""
    items = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float, str, bool))))
    run_layer, head = _jitted(items, quant)
    n = len(tokens)
    padded = np.zeros(-(-n // SEQ_BUCKET) * SEQ_BUCKET, np.int32)
    padded[:n] = tokens
    h = top["embed"][jnp.asarray(padded)].astype(F32)
    for i in range(spec["num_hidden_layers"]):
        h = run_layer(h, layer_weights(i))
    picked = np.zeros(-(-len(rows) // SEQ_BUCKET) * SEQ_BUCKET, np.int32)
    picked[:len(rows)] = rows
    return head(h, top, jnp.asarray(picked))[:len(rows)]
