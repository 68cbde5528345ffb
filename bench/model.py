"""A configuration file, the weights made from the seed, and the system
under test built from both.

A configuration is ``bench/configs/<name>.json``: the published sizes
under the source's own key names, the serving knobs under ``serve``, and
``program_arch``, the name of the program's configuration that runs it.
The program's configuration must agree with every width in the file;
only its depth and the MoE capacity factor are taken from the file.

Weights are made by the benchmark, not by the program: one jitted call
turns the seed into every leaf, in bf16, on the device, already in the
program's packed layout (``models/params.py``: at tp=1 each leaf is its
logical tensor flattened, stacked over layers). ``unpack_layer`` reads a
layer back out in logical form for the reference.
"""
from __future__ import annotations

import json
import math
import pathlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

# the program's ModelConfig field for each published key it must match
PROGRAM_KEYS = {
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "num_local_experts": "num_experts",
    "num_experts_per_tok": "experts_per_token",
}


def load_config(name: str, directory: pathlib.Path = HERE / "configs") -> dict:
    spec = json.loads((directory / f"{name}.json").read_text())
    spec.setdefault("num_local_experts", 0)
    spec.setdefault("num_experts_per_tok", 0)
    return spec


def is_moe(spec: dict) -> bool:
    return spec.get("num_local_experts", 0) > 0


def program_config(spec: dict):
    """The program's ModelConfig for ``spec``; raises where a width of the
    program's configuration differs from the file's."""
    import dataclasses

    from repro.configs import get_config

    cfg = get_config(spec["program_arch"])
    for key, field in PROGRAM_KEYS.items():
        want, have = spec[key], getattr(cfg, field)
        if want != have:
            raise ValueError(f"{spec['name']}: {key} is {want} in the file but "
                             f"{field}={have} in the program's "
                             f"{spec['program_arch']!r}")
    if spec["hidden_act"] != cfg.activation or not cfg.gated_mlp:
        raise ValueError(f"{spec['name']}: the program's MLP is not gated "
                         f"{spec['hidden_act']}")
    over = {"num_layers": spec["num_hidden_layers"]}
    if is_moe(spec):
        over["capacity_factor"] = float(spec["capacity_factor"])
    return dataclasses.replace(cfg, **over)


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------


def seed_key(seed: int, stream: int = 0x5EED):
    """A PRNG key from any whole-number seed (numpy takes big ones)."""
    k = np.random.default_rng([seed, stream]).integers(0, 2**31 - 1)
    return jax.random.PRNGKey(int(k))


def _unit(key, shape):
    """Uniform in [-0.5, 0.5), made from random bits by exact float
    operations, so any program that makes it gets the same values."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    one_two = jax.lax.bitcast_convert_type(
        (bits >> 9) | jnp.uint32(0x3F800000), jnp.float32)
    return one_two - 1.5


def _matrix(key, shape, fan_in):
    """bf16, uniform with standard deviation 1/sqrt(fan_in)."""
    return (_unit(key, shape) * (2.0 * math.sqrt(3.0 / fan_in))).astype(
        jnp.bfloat16)


def _scale(key, shape):
    """A norm's scale, uniform in [0.75, 1.25)."""
    return (_unit(key, shape) * 0.5 + 1.0).astype(jnp.bfloat16)


def layer_weights(key, spec: dict) -> Dict[str, jax.Array]:
    """One layer's logical weights (bf16)."""
    d, hd = spec["hidden_size"], spec["head_dim"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    f = spec["intermediate_size"]
    ks = jax.random.split(key, 10)
    w = {
        "attn_norm": _scale(ks[0], (d,)),
        "wq": _matrix(ks[1], (d, hq * hd), d),
        "wk": _matrix(ks[2], (d, hkv * hd), d),
        "wv": _matrix(ks[3], (d, hkv * hd), d),
        "wo": _matrix(ks[4], (hq * hd, d), hq * hd),
        "mlp_norm": _scale(ks[5], (d,)),
    }
    if is_moe(spec):
        e = spec["num_local_experts"]
        w["router"] = _matrix(ks[6], (d, e), d)
        w["w_gate"] = _matrix(ks[7], (e, d, f), d)
        w["w_up"] = _matrix(ks[8], (e, d, f), d)
        w["w_down"] = _matrix(ks[9], (e, f, d), f)
    else:
        w["w_gate"] = _matrix(ks[7], (d, f), d)
        w["w_up"] = _matrix(ks[8], (d, f), d)
        w["w_down"] = _matrix(ks[9], (f, d), f)
    return w


def top_weights(key, spec: dict) -> Dict[str, jax.Array]:
    d, v = spec["hidden_size"], spec["vocab_size"]
    k1, k2 = jax.random.split(key)
    return {"embed": _matrix(k1, (v, d), d), "final_norm": _scale(k2, (d,))}


def _pack_layer(w: dict, moe: bool) -> dict:
    """Logical layer -> the program's packed leaves (flat, tp=1)."""
    flat = lambda x: x.reshape(-1)  # noqa: E731
    attn = {"ln": w["attn_norm"], "wq": flat(w["wq"]),
            "wkv": flat(jnp.concatenate([w["wk"], w["wv"]], axis=1)),
            "wo": flat(w["wo"])}
    gate_up = jnp.concatenate([w["w_gate"], w["w_up"]], axis=-1)
    ffn = {"ln": w["mlp_norm"], "wi": flat(gate_up), "wo": flat(w["w_down"])}
    if moe:
        ffn["router"] = flat(w["router"])
    return {"attn": attn, "ffn": ffn}


def _make(key, spec_items):
    spec = dict(spec_items)
    k_top, k_layers = jax.random.split(key)
    top = top_weights(k_top, spec)
    layers = jax.lax.map(
        lambda i: _pack_layer(layer_weights(jax.random.fold_in(k_layers, i),
                                            spec), is_moe(spec)),
        jnp.arange(spec["num_hidden_layers"]))
    return {"top": {"embed": top["embed"].reshape(-1),
                    "ln_f": top["final_norm"]},
            "layers": layers}


def make_params(seed: int, spec: dict, shardings=None):
    """Every weight of the model from ``seed``: one jitted call, bf16,
    in the program's packed layout (see module docstring)."""
    items = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float, str, bool))))
    make = jax.jit(_make, static_argnums=1, out_shardings=shardings)
    return make(seed_key(seed), items)


def unpack_layer(layers: dict, i: int, spec: dict) -> Dict[str, jax.Array]:
    """Layer ``i`` of the packed stack, in logical form (as made)."""
    d, hd = spec["hidden_size"], spec["head_dim"]
    hq, hkv = spec["num_attention_heads"], spec["num_key_value_heads"]
    f = spec["intermediate_size"]
    a, m = layers["attn"], layers["ffn"]
    wkv = a["wkv"][i].reshape(d, 2 * hkv * hd)
    w = {"attn_norm": a["ln"][i], "wq": a["wq"][i].reshape(d, hq * hd),
         "wk": wkv[:, :hkv * hd], "wv": wkv[:, hkv * hd:],
         "wo": a["wo"][i].reshape(hq * hd, d), "mlp_norm": m["ln"][i]}
    if is_moe(spec):
        e = spec["num_local_experts"]
        gu = m["wi"][i].reshape(e, d, 2 * f)
        w.update(router=m["router"][i].reshape(d, e), w_gate=gu[..., :f],
                 w_up=gu[..., f:], w_down=m["wo"][i].reshape(e, f, d))
    else:
        gu = m["wi"][i].reshape(d, 2 * f)
        w.update(w_gate=gu[:, :f], w_up=gu[:, f:],
                 w_down=m["wo"][i].reshape(f, d))
    return w


def unpack_top(params: dict, spec: dict) -> Dict[str, jax.Array]:
    v, d = spec["vocab_size"], spec["hidden_size"]
    return {"embed": params["top"]["embed"].reshape(v, d),
            "final_norm": params["top"]["ln_f"]}


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def build_engine(spec: dict, seed: int, devices=None):
    """The paged serving engine of the program for ``spec``, on one chip,
    with the benchmark's weights: the two programs of
    ``launch/steps.py`` (paged decode, chunked prefill), the pools
    placed as ``launch/serve.build_paged_engine`` places them, and
    ``serve.PagedEngine`` over both. Returns (engine, programs) where
    programs maps "decode"/"prefill" to (jitted fn, example args)."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.launch.steps import (build_paged_decode_step,
                                    build_prefill_chunk_step)
    from repro.serve import PagedEngine, PagedKVCache, ServeConfig

    cfg = program_config(spec)
    s = spec["serve"]
    pcfg = ParallelConfig(dp=1, tp=1, param_dtype="bfloat16",
                          compute_dtype="bfloat16")
    devices = devices if devices is not None else jax.devices()[:1]
    mesh = Mesh(np.asarray(devices).reshape(1, 1), ("data", "model"))
    scfg = ServeConfig(batch=s["batch"], max_len=s["max_len"],
                       page_size=s["page_size"], num_pages=s["num_pages"],
                       chunk=s["chunk"], token_budget=s["token_budget"],
                       queue_cap=s["queue_cap"])
    kv = PagedKVCache(batch=scfg.batch, max_len=scfg.max_len,
                      page_size=scfg.page_size, num_pages=scfg.num_pages)
    dec = build_paged_decode_step(
        cfg, pcfg, ShapeConfig("serve", seq_len=scfg.max_len,
                               global_batch=scfg.batch, kind="decode"),
        mesh, num_pages=kv.num_pages, page_size=scfg.page_size,
        pages_per_slot=kv.pages_per_slot, cache_dtype=jnp.bfloat16)
    pre = build_prefill_chunk_step(
        cfg, pcfg, mesh, chunk=scfg.chunk, n_streams=1,
        num_pages=kv.num_pages, page_size=scfg.page_size,
        pages_per_slot=kv.pages_per_slot, cache_dtype=jnp.bfloat16)

    def shardings(specs):
        return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                            is_leaf=lambda x: isinstance(x, P))

    params = make_params(seed, spec, shardings(dec.in_pspecs[0]))
    want = jax.tree.map(lambda x: (x.shape, x.dtype), dec.in_shapes[0])
    have = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    if want != have:
        raise ValueError(f"the program's parameter layout changed: it takes "
                         f"{want}, the benchmark makes {have}")
    pools = jax.tree.map(lambda x, sh: jnp.zeros(x.shape, x.dtype, device=sh),
                         dec.in_shapes[1], shardings(dec.in_pspecs[1]))
    eng = PagedEngine(pre.fn, dec.fn, params, pools, scfg, dp_shards=1,
                      eos_id=-1, seed=seed, pcfg=pcfg)
    b, p, c = scfg.batch, kv.pages_per_slot, scfg.chunk
    i32 = np.int32
    programs = {
        "decode": (dec.fn, (np.zeros((b, p), i32), np.zeros(b, i32),
                            np.zeros(b, bool), np.zeros((b, 1), i32))),
        "prefill": (pre.fn, (np.zeros((1, p), i32), np.zeros(1, i32),
                             np.zeros(1, i32), np.zeros((1, c), i32))),
    }
    return eng, programs
