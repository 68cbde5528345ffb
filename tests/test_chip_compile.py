"""The main path's kernels compiled for a TPU v5e that is described, not
attached: the TPU compiler refuses here what the chip would refuse
(tiling, scoped VMEM, collective ids), at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers
must all collect the same tests. Keep every such compile in this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

W = 4  # granite-3-2b at tp=4: 512 rows per rank, d_model 2048
ROWS, D = 512 * W, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def tp_mesh(topo):
    return Mesh(np.asarray(topo.devices[:W]), ("tp",))


def _kernel_compiles(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("s_len", [2048, 2000], ids=["pages", "ragged"])
def test_flash_decode_compiles_at_serving_shapes(one_chip, s_len):
    """chip_smoke's paged decode: batch 8, 32q/8kv x 64, 2048 positions
    (128 pages of 16); 2000 checks a last KV tile the cache cuts short."""
    from repro.kernels import flash_decode as fd

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    _kernel_compiles(fd.flash_decode, s((8, 32, 64)), s((8, 8, s_len, 64)),
                     s((8, 8, s_len, 64)), s((8,), jnp.int32))


@pytest.mark.parametrize("hkv,d", [(8, 64), (2, 64), (8, 128)],
                         ids=["tp1", "tp4", "hd128"])
def test_paged_flash_decode_compiles_at_serving_shapes(one_chip, hkv, d):
    """The benchmark's paged decode: 32 slots, 2800 pages of 16, 256 pages
    a slot, 32q/8kv x 64 (a 64-wide head read lane-dense); at tp=4 each
    rank holds 2 KV heads; and a 128-wide head."""
    from repro.kernels import flash_decode as fd

    def s(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = s((2800, hkv, *fd.page_rows(16, d)))
    _kernel_compiles(fd.paged_flash_decode, s((32, 4 * hkv, d)), pool, pool,
                     s((32, 256), jnp.int32), s((32,), jnp.int32))


@pytest.mark.parametrize("b,l", [(1, 2048), (4, 512)])
def test_flash_attention_compiles(one_chip, b, l):
    from repro.kernels import flash_attention as fa

    q = jax.ShapeDtypeStruct((b, 32, l, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, 8, l, 64), jnp.bfloat16, sharding=one_chip)
    _kernel_compiles(lambda q_, k_, v_: fa.flash_attention(q_, k_, v_),
                     q, kv, kv)


@pytest.mark.parametrize("op,width", [
    ("ag_matmul", 768),    # QKV: 2048 -> (32 + 2*8) * 64 / 4
    ("ag_matmul", 4096),   # gated MLP-in: 2048 -> 2 * 8192 / 4
    ("matmul_rs", 512),    # attention out: 32 * 64 / 4 -> 2048
    ("matmul_rs", 2048),   # MLP out: 8192 / 4 -> 2048
], ids=["qkv", "mlp_in", "attn_out", "mlp_out"])
def test_ring_protocols_compile_at_tp4_widths(tp_mesh, monkeypatch, op,
                                              width):
    """ring_ag (AG+GEMM) and push_rs (GEMM+RS) on four described chips:
    the pltpu executor kernels with their scoped-VMEM budget."""
    from repro import ops

    monkeypatch.setenv("REPRO_SHMEM_BACKEND", "pltpu")
    if op == "ag_matmul":
        shapes, specs, out = ((ROWS, D), (D, width * W)), \
            (P("tp", None), P(None, "tp")), P(None, "tp")
    else:
        shapes, specs, out = ((ROWS, width * W), (width * W, D)), \
            (P(None, "tp"), P("tp", None)), P("tp", None)
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16,
                                 sharding=NamedSharding(tp_mesh, sp))
            for s, sp in zip(shapes, specs)]
    fn = jax.shard_map(
        lambda a, b: getattr(ops, op)(a, b, axis="tp", mode="ring",
                                      backend="kernel",
                                      out_dtype=jnp.bfloat16),
        mesh=tp_mesh, in_specs=specs, out_specs=out, check_vma=False)
    _kernel_compiles(fn, *args)


def _compiled_step(built, mesh) -> str:
    """The HLO of a built serving step compiled for the described chips:
    parameters and pools placed by its specs, the host inputs
    replicated."""
    rep = NamedSharding(mesh, P())
    shapes = list(built.in_shapes)
    for i, specs in enumerate(built.in_pspecs):
        shapes[i] = jax.tree.map(
            lambda s, sp: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
            shapes[i], specs, is_leaf=lambda x: isinstance(x, P))
    shapes[2:] = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep)
                  for s in shapes[2:]]
    return built.fn.lower(*shapes).compile().as_text()


def test_paged_decode_step_compiles_with_pallas(topo, monkeypatch):
    """One paged-decode step of granite-3-2b at full width and depth 2,
    bf16, with the kernels steered to the chip's path: the compiled step
    holds the paged flash-decode kernel and no whole-table gather (no
    instruction under the ``paged_gather`` scope)."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.kernels import ops as kops
    from repro.launch.steps import build_paged_decode_step

    monkeypatch.setattr(kops, "_platform", lambda: "tpu")
    cfg = dataclasses.replace(get_config("granite-3-2b"), num_layers=2)
    pcfg = ParallelConfig(dp=1, tp=1, param_dtype="bfloat16",
                          compute_dtype="bfloat16")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    batch, max_len, page = 8, 2048, 16
    built = build_paged_decode_step(
        cfg, pcfg, ShapeConfig("serve", seq_len=max_len, global_batch=batch,
                               kind="decode"),
        mesh, num_pages=batch * max_len // page + 1, page_size=page,
        pages_per_slot=max_len // page, cache_dtype=jnp.bfloat16)
    text = _compiled_step(built, mesh)
    assert "tpu_custom_call" in text
    names = re.findall(r'op_name="([^"]*)"', text)
    assert any("/attn/flash_decode/" in n for n in names)
    assert not any("/paged_gather/" in n for n in names)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_moe_paged_programs_compile_with_pallas(topo, monkeypatch, kind):
    """granite-moe-3b-a800m at full width and depth 2, bf16, Pallas on:
    the benchmark's paged decode (32 slots) and 256-token chunked
    prefill compile for a v5e, each holding the experts' grouped-GEMM
    kernels under the ``moe/experts`` scope."""
    import dataclasses

    from repro.configs import get_config
    from repro.configs.base import ParallelConfig, ShapeConfig
    from repro.kernels import ops as kops
    from repro.launch.steps import (build_paged_decode_step,
                                    build_prefill_chunk_step)

    monkeypatch.setattr(kops, "_platform", lambda: "tpu")
    cfg = dataclasses.replace(get_config("granite-moe-3b-a800m"), num_layers=2)
    pcfg = ParallelConfig(dp=1, tp=1, param_dtype="bfloat16",
                          compute_dtype="bfloat16")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    batch, max_len, page, pages = 32, 4096, 16, 4000
    geometry = dict(num_pages=pages, page_size=page,
                    pages_per_slot=max_len // page, cache_dtype=jnp.bfloat16)
    if kind == "decode":
        built = build_paged_decode_step(
            cfg, pcfg, ShapeConfig("serve", seq_len=max_len,
                                   global_batch=batch, kind="decode"),
            mesh, **geometry)
    else:
        built = build_prefill_chunk_step(cfg, pcfg, mesh, chunk=256,
                                         n_streams=1, **geometry)
    text = _compiled_step(built, mesh)
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "/moe/" in line]
    assert sum(bool(re.search(r"/moe/(checkpoint/)?experts/", line))
               for line in kernels) == 2
