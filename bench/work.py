"""Useful work of the serving programs and their kernels, counted from
the configuration's shapes and the host's lengths, whatever kernel does
it: padding lanes, padded capacity and positions past a slot's length
are not work.

Matrix products count 2 operations per multiply-add. Attention over a
context of c keys counts 4 * heads * head_dim * c per query (scores and
weighted values). Bytes count what has to cross HBM at least once.
"""
from __future__ import annotations

from typing import Iterable, Tuple

BF16 = 2  # bytes


def projection_flops_per_token(spec: dict) -> float:
    """Every weight matrix a token passes through in one layer, except
    the embedding (a lookup) and the output head (counted per row)."""
    d, hd = spec["hidden_size"], spec["head_dim"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    f = spec["intermediate_size"]
    attn = d * hq * hd + 2 * d * hkv * hd + hq * hd * d
    if spec.get("num_local_experts", 0):
        mlp = d * spec["num_local_experts"] + \
            spec["num_experts_per_tok"] * 3 * d * f
    else:
        mlp = 3 * d * f
    return 2.0 * (attn + mlp)


def attention_flops(spec: dict, contexts: Iterable[int]) -> float:
    """One layer's attention for queries attending ``contexts`` keys."""
    return 4.0 * spec["num_attention_heads"] * spec["head_dim"] * \
        float(sum(contexts))


def head_flops(spec: dict, rows: int) -> float:
    return 2.0 * spec["hidden_size"] * spec["vocab_size"] * rows


def decode_flops(spec: dict, lengths: Iterable[int]) -> float:
    """A decode call over the active slots, each with ``length`` tokens
    already cached (it attends length + 1)."""
    lengths = list(lengths)
    ctx = [n + 1 for n in lengths]
    per_layer = projection_flops_per_token(spec) * len(lengths) + \
        attention_flops(spec, ctx)
    return spec["num_hidden_layers"] * per_layer + head_flops(spec, len(lengths))


def prefill_flops(spec: dict, start: int, n: int) -> float:
    """A prefill chunk of ``n`` prompt tokens at positions start.. :
    token j attends start + j + 1 keys; one row of logits."""
    ctx = range(start + 1, start + n + 1)
    per_layer = projection_flops_per_token(spec) * n + attention_flops(spec, ctx)
    return spec["num_hidden_layers"] * per_layer + head_flops(spec, 1)


def flash_decode_work(spec: dict, lengths: Iterable[int]) -> Tuple[float, float]:
    """(flops, bytes) of the decode-attention kernel in one decode call,
    over all layers: the active slots' K and V at their real lengths
    (read once) and their queries and outputs."""
    ctx = [n + 1 for n in lengths]
    hq, hkv, hd = (spec["num_attention_heads"], spec["num_key_value_heads"],
                   spec["head_dim"])
    layers = spec["num_hidden_layers"]
    flops = layers * attention_flops(spec, ctx)
    kv = 2 * hkv * hd * BF16 * sum(ctx)
    qo = len(ctx) * hq * hd * (BF16 + 4)
    return flops, float(layers * (kv + qo))


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(flops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
