"""Wire-dtype axis tests (quantized riding chunks).

1. Codec pins: ``ops.wire.encode`` int8 is bit-identical to the legacy
   ``dist/compress.py`` per-row formula (which now delegates to it), and
   pack/unpack round-trips the split representation exactly.
2. Policy/config validation: unknown wire dtypes raise eagerly with the
   valid set in the message; the explicit-policy-vs-legacy-fields
   conflict covers ``overlap_wire`` in both argument orders; resolution
   clamps wires off baseline modes, two_level and non-wire-capable ops.
3. Graph-vs-kernel and quantized-vs-f32 parity for every wire-capable
   (op, transport) at worlds 2/4/8. Documented tolerances (relative
   error vs the f32 graph baseline): int8 <= 5e-2, fp8 <= 1e-1 — the
   empirical errors on randn inputs are ~5x under these.
4. Backward: with a linear loss (constant cotangent) the int8-wire
   grads are bit-identical across graph/kernel forwards (the shared
   custom_vjp keeps ONE dual schedule), and close to the f32 grads.
5. Error feedback: repeated int8 reductions WITH feedback beat the
   same reductions without (satellite of ``pod_allreduce_int8``).
6. Tuner: the analytic models enumerate mode x chunks x wire and pick
   int8 only where the ICI-bytes term binds.
"""
import textwrap

import numpy as np
import pytest

from conftest import run_devices


# ---------------------------------------------------------------------------
# 1. codec pins (single device, in-process)
# ---------------------------------------------------------------------------


def test_codec_pins_legacy_formula_and_roundtrip():
    import jax.numpy as jnp

    from repro.dist import compress
    from repro.ops import wire

    rng = np.random.RandomState(0)
    g = jnp.asarray(rng.randn(16, 33) * 3.0, jnp.float32)

    # the exact legacy dist/compress.py recipe, inlined as the reference
    gf = np.asarray(g, np.float32)
    scale_ref = np.maximum(np.abs(gf).max(axis=-1, keepdims=True) / 127.0,
                           1e-12)
    q_ref = np.clip(np.round(gf / scale_ref), -127.0, 127.0).astype(np.int8)

    q, s = wire.encode(g, "int8")
    assert np.array_equal(np.asarray(q), q_ref)
    assert np.array_equal(np.asarray(s), scale_ref.astype(np.float32))
    # compress.quantize_int8 IS the shared codec now — pin the equality
    q2, s2 = compress.quantize_int8(g)
    assert np.array_equal(np.asarray(q2), np.asarray(q))
    assert np.array_equal(np.asarray(s2), np.asarray(s))
    assert np.array_equal(np.asarray(compress.dequantize_int8(q, s)),
                          np.asarray(wire.decode(q, s)))

    # pack/unpack is an exact round-trip of the split representation
    for w in ("int8", "fp8"):
        p, sc = wire.encode(g, w)
        buf = wire.pack(p, sc)
        assert buf.dtype == jnp.uint8
        assert buf.shape == (16, 33 + wire.SCALE_BYTES)
        p2, sc2 = wire.unpack(buf, w)
        assert np.array_equal(np.asarray(p2), np.asarray(p))
        assert np.array_equal(np.asarray(sc2), np.asarray(sc))
        c = wire.codec(w)
        assert np.array_equal(np.asarray(c.unpack_decode(buf)),
                              np.asarray(wire.decode(p, sc)))

    assert wire.codec("f32") is None
    with pytest.raises(ValueError, match="int4"):
        wire.codec("int4")
    # bytes model: 1-byte payload + one f32 scale per row
    assert wire.wire_bytes(8, 32, "f32", 4) == 8 * 32 * 4
    assert wire.wire_bytes(8, 32, "int8", 4) == 8 * (32 + 4)
    assert wire.wire_bytes(8, 32, "fp8", 2) == 8 * (32 + 4)


# ---------------------------------------------------------------------------
# 2. policy / config validation and resolution
# ---------------------------------------------------------------------------


def test_policy_wire_validation_and_resolution():
    from repro import ops

    with pytest.raises(ValueError, match=r"int4.*valid.*f32"):
        ops.OverlapPolicy(wire="int4")
    with pytest.raises(ValueError, match=r"int4.*valid"):
        ops.OverlapPolicy(wires={"ag_matmul": "int4"})

    pol = ops.OverlapPolicy(mode="ring", wire="int8")
    assert pol.resolve("ag_matmul").wire == "int8"
    assert pol.resolve("matmul_rs").wire == "int8"
    # baseline mode rides XLA collectives — no riding chunks to quantize
    assert ops.OverlapPolicy(mode="none", wire="int8") \
        .resolve("ag_matmul").wire == "f32"
    # non-wire-capable ops clamp to f32 under a global int8 default
    assert pol.resolve("flash_decode").wire == "f32"
    assert pol.resolve("ag_matmul_2level").wire == "f32"
    # per-op override beats the global default
    pol2 = ops.OverlapPolicy(mode="ring", wires={"matmul_rs": "fp8"})
    assert pol2.resolve("matmul_rs").wire == "fp8"
    assert pol2.resolve("ag_matmul").wire == "f32"
    assert "fp8" in pol2.describe("matmul_rs")


def test_parallel_config_wire_field_and_conflict():
    from repro import ops
    from repro.configs.base import ParallelConfig

    with pytest.raises(ValueError, match=r"int4.*valid"):
        ParallelConfig(tp=4, overlap_wire="int4")
    cfg = ParallelConfig(tp=4, overlap_mode="ring", overlap_wire="int8")
    assert cfg.policy.resolve("ag_matmul").wire == "int8"

    # explicit policy + non-default legacy wire field = two sources of
    # truth -> ValueError, BOTH argument orders (PR 4 pattern)
    pol = ops.OverlapPolicy(mode="ring", wire="int8")
    with pytest.raises(ValueError, match="overlap_wire"):
        ParallelConfig(tp=4, overlap=pol, overlap_wire="int8")
    with pytest.raises(ValueError, match="overlap_wire"):
        ParallelConfig(tp=4, overlap_wire="int8", overlap=pol)
    # a policy carrying the wire is the one source of truth — fine
    assert ParallelConfig(tp=4, overlap=pol) \
        .policy.resolve("ag_matmul").wire == "int8"


def test_registry_wire_capability():
    from repro.core import overlap as ov

    for op in ("ag_matmul", "matmul_rs", "all_gather", "reduce_scatter",
               "a2a_ep", "ring_attention"):
        assert ov.wires_for(op) == ("f32", "int8", "fp8"), op
    for op in ("flash_decode", "ag_matmul_2level"):
        assert ov.wires_for(op) == ("f32",), op
    with pytest.raises(ValueError, match="int4"):
        ov.resolve_wire("ag_matmul", "int4")
    assert ov.resolve_wire("ag_matmul", "int8", "ring") == "int8"
    assert ov.resolve_wire("ag_matmul", "int8", "none") == "f32"
    assert ov.resolve_wire("flash_decode", "int8", "one_shot") == "f32"
    # fold ops ride a multi-section packed chunk (K|V): wire-capable too
    assert ov.resolve_wire("ring_attention", "int8", "ring") == "int8"


# ---------------------------------------------------------------------------
# 3. quantized parity: graph vs kernel vs f32 baseline, worlds 2/4/8
# ---------------------------------------------------------------------------

# documented tolerances (relative error vs the f32 graph baseline)
_TOL = {"int8": 5e-2, "fp8": 1e-1}

PARITY = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro import ops
    from repro.core import moe_overlap as mo

    W = __WORLD__
    mesh = jax.make_mesh((W,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    TOL = {"int8": 5e-2, "fp8": 1e-1}

    def sh(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return np.abs(a - b).max() / max(1e-9, np.abs(b).max())

    M, K, N = 8 * W, 16, 4 * W
    A = jnp.asarray(rng.randn(M, K), jnp.float32)
    Wt = jnp.asarray(rng.randn(K, N), jnp.float32)

    def check(tag, got, ref, wire):
        e = rel(got, ref)
        assert e <= TOL[wire], f"{tag}: rel_err={e:.4f} > {TOL[wire]}"

    # ---- ag_matmul: riding A-chunks quantized ----
    AG = ((P("tp", None), P(None, "tp")), P(None, "tp"))
    ref = sh(functools.partial(ops.ag_matmul, axis="tp", mode="ring",
                               out_dtype=jnp.float32), *AG)(A, Wt)
    for mode in ("ring", "bidir", "one_shot"):
        for backend in ("graph", "kernel"):
            for wire in ("int8", "fp8"):
                if wire == "fp8" and mode != "ring":
                    continue  # fp8 pinned on one transport per op
                f = sh(functools.partial(ops.ag_matmul, axis="tp", mode=mode,
                                         backend=backend, wire=wire,
                                         out_dtype=jnp.float32), *AG)
                check(f"ag_matmul/{mode}/{backend}/{wire}", f(A, Wt), ref, wire)

    # ---- matmul_rs: riding partial accumulators quantized ----
    RS = ((P(None, "tp"), P("tp", None)), P("tp", None))
    ref = sh(functools.partial(ops.matmul_rs, axis="tp", mode="ring",
                               out_dtype=jnp.float32), *RS)(A, Wt)
    for mode in ("ring", "bidir", "one_shot"):
        for backend in ("graph", "kernel"):
            f = sh(functools.partial(ops.matmul_rs, axis="tp", mode=mode,
                                     backend=backend, wire="int8",
                                     out_dtype=jnp.float32), *RS)
            check(f"matmul_rs/{mode}/{backend}/int8", f(A, Wt), ref, "int8")

    # ---- stand-alone collectives ----
    X = jnp.asarray(rng.randn(4 * W, 8), jnp.float32)
    C = (P("tp", None), P(None, None))
    ref = sh(functools.partial(ops.all_gather, axis="tp", mode="ring"),
             *C)(X)
    for backend in ("graph", "kernel"):
        f = sh(functools.partial(ops.all_gather, axis="tp", mode="ring",
                                 backend=backend, wire="int8"), *C)
        check(f"all_gather/ring/{backend}/int8", f(X), ref, "int8")

    Y = jnp.asarray(rng.randn(4 * W, 8), jnp.float32)
    C = (P(None, None), P("tp", None))
    ref = sh(functools.partial(ops.reduce_scatter, axis="tp", mode="ring"),
             *C)(Y)
    for mode in ("ring", "one_shot"):
        for backend in ("graph", "kernel"):
            f = sh(functools.partial(ops.reduce_scatter, axis="tp", mode=mode,
                                     backend=backend, wire="int8"), *C)
            check(f"reduce_scatter/{mode}/{backend}/int8", f(Y), ref, "int8")

    # ---- a2a_ep: riding token slabs quantized ----
    E, cap, d = 2 * W, 4, 16
    Xd = jnp.asarray(rng.randn(W * E, cap, d), jnp.float32)
    C = (P("tp", None, None), P("tp", None, None))
    ref = sh(functools.partial(mo.a2a_ep, axis="tp", mode="one_shot"),
             *C)(Xd)
    for backend in ("graph", "kernel"):
        f = sh(functools.partial(mo.a2a_ep, axis="tp", mode="one_shot",
                                 backend=backend, wire="int8"), *C)
        check(f"a2a_ep/one_shot/{backend}/int8", f(Xd), ref, "int8")

    # ---- ring_attention: riding packed K|V chunk, per-section scales ----
    B, H, HKV, D = 2, 4, 2, 16
    S = 8 * W
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32)
    kv = jnp.asarray(rng.randn(B, HKV, S, 2 * D), jnp.float32)
    AT = ((P(None, None, "tp", None), P(None, None, "tp", None)),
          P(None, None, "tp", None))
    attn = functools.partial(ops.ring_attention, axis="tp", causal=True,
                             scale=float(1.0 / np.sqrt(D)),
                             out_dtype=jnp.float32)
    ref = sh(functools.partial(attn, mode="ring"), *AT)(kv, q)
    for mode in ("ring", "one_shot"):
        for backend in ("graph", "kernel"):
            for wire in ("int8", "fp8"):
                if wire == "fp8" and mode != "ring":
                    continue
                f = sh(functools.partial(attn, mode=mode, backend=backend,
                                         wire=wire), *AT)
                check(f"ring_attention/{mode}/{backend}/{wire}",
                      f(kv, q), ref, wire)

    print("OK")
""")


@pytest.mark.parametrize("world", [2, 4, 8])
def test_quantized_parity_all_wire_ops(world):
    out = run_devices(PARITY.replace("__WORLD__", str(world)), devices=world)
    assert "OK" in out


# ---------------------------------------------------------------------------
# 4. backward under a quantized wire
# ---------------------------------------------------------------------------

GRADS = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro import ops

    W = __WORLD__
    mesh = jax.make_mesh((W,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)

    M, K, N = 8 * W, 16, 4 * W
    A = jnp.asarray(rng.randn(M, K), jnp.float32)
    Wt = jnp.asarray(rng.randn(K, N), jnp.float32)

    for op, in_specs in (
        (ops.ag_matmul, (P("tp", None), P(None, "tp"))),
        (ops.matmul_rs, (P(None, "tp"), P("tp", None))),
    ):
        def make_grad(backend, wire):
            def f(a, w):
                # linear loss -> constant cotangent: the dual schedule's
                # output is bit-identical across forward backends
                out = op(a, w, axis="tp", mode="ring", backend=backend,
                         wire=wire, out_dtype=jnp.float32)
                return lax.psum(jnp.sum(out), "tp")
            return jax.jit(jax.shard_map(
                jax.grad(f, argnums=(0, 1)), mesh=mesh,
                in_specs=in_specs, out_specs=in_specs, check_vma=False))

        g_f32 = make_grad("graph", "f32")(A, Wt)
        g_g = make_grad("graph", "int8")(A, Wt)
        g_k = make_grad("kernel", "int8")(A, Wt)
        for gg, gk, gf in zip(g_g, g_k, g_f32):
            gg, gk, gf = map(np.asarray, (gg, gk, gf))
            assert np.all(np.isfinite(gg))
            # ONE dual schedule: kernel fwd keeps the graph dual
            assert np.array_equal(gg, gk), op
            # duals ride the same wire -> close to f32 grads
            err = np.abs(gg - gf).max() / max(1e-9, np.abs(gf).max())
            assert err <= 5e-2, f"{op}: grad rel_err={err:.4f}"
    print("OK")
""")


def test_quantized_wire_grads_bit_identical_across_backends():
    out = run_devices(GRADS.replace("__WORLD__", "4"), devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# 5. error feedback beats no feedback over repeated reductions
# ---------------------------------------------------------------------------

EF = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist import compress

    W = 4
    mesh = jax.make_mesh((W,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    G = jnp.asarray(rng.randn(W, 8, 64) * 0.1, jnp.float32)
    true = np.asarray(G, np.float64).sum(axis=0)

    step = jax.jit(jax.shard_map(
        functools.partial(compress.pod_allreduce_int8, axis="pod"),
        mesh=mesh, in_specs=(P("pod", None, None), P("pod", None, None)),
        out_specs=(P("pod", None, None), P("pod", None, None)),
        check_vma=False))

    def run(feedback, steps=8):
        ef = jnp.zeros_like(G)
        acc = np.zeros_like(true)
        for _ in range(steps):
            total, new_ef = step(G, ef)
            if feedback:
                ef = new_ef
            acc += np.asarray(total[0], np.float64)
        return np.abs(acc / steps - true).mean()

    err_with, err_without = run(True), run(False)
    # with feedback the residual is re-injected next step, so the TIME-
    # AVERAGED sum converges; without it the same bias repeats every step
    assert err_with < err_without * 0.5, (err_with, err_without)
    print("OK", err_with, err_without)
""")


def test_error_feedback_convergence():
    out = run_devices(EF, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# 6. tuner enumerates the wire axis
# ---------------------------------------------------------------------------


def test_tuner_wire_axis():
    from repro.core import tuner

    # f32 operands, tiny per-chunk compute: ICI bytes bind -> int8 wins
    comm = tuner.analytic_ag_matmul(1024, 4096, 256, 8, dtype_bytes=4)
    assert comm.wire == "int8"
    # big n_loc: MXU time dominates, codec passes make int8 a loss
    comp = tuner.analytic_ag_matmul(1024, 4096, 16384, 8, dtype_bytes=4)
    assert comp.wire == "f32"

    # matmul_rs rides an f32 accumulator, so even bf16 problems compress
    rs_comm = tuner.analytic_matmul_rs(8192, 256, 4096, 8)
    assert rs_comm.wire == "int8"
    rs_comp = tuner.analytic_matmul_rs(8192, 8192, 4096, 8)
    assert rs_comp.wire == "f32"

    # recommend_overlap_modes lands wire picks as per-op policy entries
    pol = tuner.recommend_overlap_modes(8192, 4096, 2048, 8)
    assert pol.resolve("matmul_rs").wire == "int8"
    assert pol.resolve("a2a_ep").wire == "f32"  # no analytic pick -> f32
