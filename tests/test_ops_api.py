"""repro.ops authoring-API tests.

1. Declaring a toy op IN-TEST via ``OverlapOp`` auto-appears in the
   engine registry with derived graph + kernel lowerings and the derived
   dual-schedule backward; it passes graph-vs-kernel parity at worlds
   2/4/8 and round-trips grads bit-identically through the ONE shared
   custom_vjp (kernel forward keeps the graph dual as its backward).
2. ``ops.fuse``: the fused rs->ag boundary declaration
   (``matmul_rs_ag_matmul``) matches the composed unfused pair in values
   AND grads at worlds 2/4/8, on both backends, with grads bit-identical
   across backends (the backward recomputes on a fixed graph path).
3. ``OverlapPolicy``: single-point resolution (mode clamped by the
   registry, backend degraded off kernel-incapable pairs, chunk count
   picked by op kind), dict ergonomics, hw-aware degrade.
"""
import dataclasses
import textwrap

import pytest

from conftest import run_devices

TOY = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro import ops
    from repro.core import overlap as ov

    W = __WORLD__
    mesh = jax.make_mesh((W,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)

    # ---- declare toy ops IN-TEST (nonlinear in the static operand) ----
    assert "toy_ag" not in ov.registry()
    toy_tile = lambda c, w: jnp.dot(c, jnp.tanh(w),
                                    preferred_element_type=jnp.float32)
    toy_ag = ops.declare(ops.OverlapOp(
        name="toy_ag", kind="ag", tile=toy_tile,
        transports=("ring", "bidir", "one_shot"),
        kernel_protocols=(("ring", "ring_ag"), ("bidir", "bidir_ring_ag"),
                          ("one_shot", "one_shot_ag")),
        transpose="matmul_rs", rowwise=True))
    toy_rs = ops.declare(ops.OverlapOp(
        name="toy_rs", kind="rs", tile=toy_tile,
        transports=("ring", "one_shot"),
        kernel_protocols=(("ring", "push_rs"), ("one_shot", "one_shot_rs")),
        transpose="toy_ag"))

    # auto-registration: spec with derived fwd/bwd/kernel_fwd appears
    spec = ov.get("toy_ag")
    assert spec.kind == "ag"
    assert spec.kernel_transports == ("ring", "bidir", "one_shot")
    assert spec.fwd is not None and spec.bwd is not None
    assert spec.kernel_fwd is not None
    # ...and is immediately visible to tuner candidate enumeration and
    # policy resolution, with no extra wiring
    assert ov.transports_for("toy_ag") == ("ring", "bidir", "one_shot")
    assert ov.backends_for("toy_rs") == ("graph", "kernel")
    pol = ops.OverlapPolicy(mode="ring", backend="kernel")
    assert pol.resolve("toy_ag").backend == "kernel"
    assert pol.resolve("toy_rs").backend == "kernel"
    assert pol.resolve("toy_ag", hw=None).mode == "ring"

    def sh(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    M, K, N = 4 * W, 8, 2 * W
    A = jnp.asarray(rng.randn(M, K), jnp.float32)
    Wt = jnp.asarray(rng.randn(K, N), jnp.float32)
    want = np.asarray(A) @ np.tanh(np.asarray(Wt))

    AG_SPECS = ((P("tp", None), P(None, "tp")), P(None, "tp"))
    # derived graph lowering matches the oracle on every transport
    for mode in ("none", "ring", "bidir", "one_shot"):
        f = sh(functools.partial(toy_ag, axis="tp", mode=mode,
                                 out_dtype=jnp.float32), *AG_SPECS)
        err = np.abs(np.asarray(f(A, Wt)) - want).max()
        assert err < 2e-4, ("toy_ag", mode, err)

    # graph-vs-kernel parity for every declared (transport, protocol)
    def run(op, specs, mode, backend, *xs):
        f = sh(functools.partial(op, axis="tp", mode=mode, backend=backend,
                                 out_dtype=jnp.float32), *specs)
        return np.asarray(f(*xs))

    for mode in ("ring", "bidir", "one_shot"):
        k = run(toy_ag, AG_SPECS, mode, "kernel", A, Wt)
        g = run(toy_ag, AG_SPECS, mode, "graph", A, Wt)
        assert np.abs(k - g).max() < 2e-4, ("toy_ag kernel", mode)

    RS_SPECS = ((P(None, "tp"), P("tp", None)), P("tp", None))
    A2 = jnp.asarray(rng.randn(M, 4 * W), jnp.float32)
    W2 = jnp.asarray(rng.randn(4 * W, N), jnp.float32)
    want2 = np.asarray(A2) @ np.tanh(np.asarray(W2))
    for mode in ("none", "ring", "one_shot"):
        g = run(toy_rs, RS_SPECS, mode, "graph", A2, W2)
        assert np.abs(g - want2).max() < 2e-4, ("toy_rs", mode)
    for mode in ("ring", "one_shot"):
        k = run(toy_rs, RS_SPECS, mode, "kernel", A2, W2)
        g = run(toy_rs, RS_SPECS, mode, "graph", A2, W2)
        assert np.abs(k - g).max() < 2e-4, ("toy_rs kernel", mode)

    # grads round-trip the SHARED custom_vjp bit-identically across
    # backends (kernel fwd keeps the graph dual as its backward), and
    # match autodiff of the unfused oracle
    def make_grad(backend, mode="ring"):
        def f(a, w):
            out = toy_ag(a, w, axis="tp", mode=mode, backend=backend,
                         out_dtype=jnp.float32)
            return lax.psum(jnp.sum(out * out), "tp")
        return sh(jax.grad(f, argnums=(0, 1)),
                  (P("tp", None), P(None, "tp")),
                  (P("tp", None), P(None, "tp")))

    gg = [np.asarray(t) for t in make_grad("graph")(A, Wt)]
    gk = [np.asarray(t) for t in make_grad("kernel")(A, Wt)]
    for a, b in zip(gg, gk):
        assert np.array_equal(a, b), "toy_ag grads differ across backends"
    for a, b in zip(make_grad("graph", "bidir")(A, Wt),
                    make_grad("kernel", "bidir")(A, Wt)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "toy_ag bidir grads differ across backends"

    def oracle(a, w):
        out = jnp.dot(lax.all_gather(a, "tp", tiled=True), jnp.tanh(w),
                      preferred_element_type=jnp.float32)
        return lax.psum(jnp.sum(out * out), "tp")

    go = sh(jax.grad(oracle, argnums=(0, 1)),
            (P("tp", None), P(None, "tp")),
            (P("tp", None), P(None, "tp")))(A, Wt)
    for a, b in zip(gg, [np.asarray(t) for t in go]):
        assert np.abs(a - b).max() < 1e-3, "toy_ag grads vs oracle"
    print("OK toy ops", W)
""")


@pytest.mark.parametrize("world", [2, 4, 8])
def test_toy_op_declaration_registry_parity_grads(world):
    out = run_devices(TOY.replace("__WORLD__", str(world)), devices=world,
                      timeout=1200)
    assert "OK" in out


FUSED = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro import ops

    W = __WORLD__
    mesh = jax.make_mesh((W,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)

    M, K, N, F = 4 * W, 2 * W, 6, 3 * W
    Y = jnp.asarray(rng.randn(M, K), jnp.float32)
    WO = jnp.asarray(rng.randn(K, N), jnp.float32)
    WI = jnp.asarray(rng.randn(N, F), jnp.float32)
    XR = jnp.asarray(rng.randn(M, N), jnp.float32)

    def boundary(r, x):
        # rank-local seam: residual add + nonlinearity (rows stay rows)
        return jnp.tanh(r + x.astype(r.dtype))

    IN = (P(None, "tp"), P("tp", None), P(None, "tp"), P("tp", None))
    OUT = P(None, "tp")

    def sh(fn, in_specs=IN, out_specs=OUT):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def run(mode, backend="graph", chunks=1):
        f = sh(functools.partial(
            ops.matmul_rs_ag_matmul, axis="tp", mode=mode, backend=backend,
            chunks=chunks, out_dtype=jnp.float32, mid=boundary))
        return np.asarray(f(Y, WO, WI, XR))

    # the composed unfused pair on XLA collectives is the oracle; the
    # documented tolerance vs every fused lowering is f32-accumulation
    # rounding (identical FLOPs, reassociated across the seam)
    def composed(y, wo, wi, x):
        r = ops.matmul_rs(y, wo, axis="tp", mode="none",
                          out_dtype=jnp.float32)
        h = boundary(r, x)
        return ops.ag_matmul(h, wi, axis="tp", mode="none",
                             out_dtype=jnp.float32)

    want = np.asarray(sh(composed)(Y, WO, WI, XR))
    # mode "none" IS the registered composed-pair baseline
    assert np.abs(run("none") - want).max() < 1e-5, "baseline vs composed"
    for label, out in (("ring", run("ring")),
                       ("ring-x2", run("ring", chunks=2)),
                       ("one_shot", run("one_shot"))):
        assert np.abs(out - want).max() < 1e-5, ("fused graph", label)

    # graph-vs-kernel parity on the chained push_rs -> ring_ag protocol
    for chunks in (1, 2):
        k = run("ring", backend="kernel", chunks=chunks)
        g = run("ring", backend="graph", chunks=chunks)
        assert np.abs(k - g).max() < 1e-5, ("fused kernel parity", chunks)

    # grads: fused-vs-composed close under a quadratic loss; graph-vs-
    # kernel bit-identical under a FIXED cotangent (linear loss) — the
    # shared custom_vjp recomputes on a fixed graph path, so the
    # backward never depends on which backend ran the forward
    GSPECS = dict(in_specs=IN, out_specs=IN)

    def make_grad(fn, quad=True):
        def loss(y, wo, wi, x):
            out = fn(y, wo, wi, x)
            return lax.psum(jnp.sum(out * out if quad else out), "tp")
        return jax.jit(jax.shard_map(jax.grad(loss, argnums=(0, 1, 2, 3)),
                                     mesh=mesh, check_vma=False, **GSPECS))

    def fused_fn(backend):
        return functools.partial(
            ops.matmul_rs_ag_matmul, axis="tp", mode="ring", backend=backend,
            out_dtype=jnp.float32, mid=boundary)

    go = [np.asarray(t) for t in make_grad(composed)(Y, WO, WI, XR)]
    gg = [np.asarray(t) for t in make_grad(fused_fn("graph"))(Y, WO, WI, XR)]
    for a, b in zip(gg, go):
        rel = np.abs(a - b).max() / max(1.0, np.abs(b).max())
        assert rel < 1e-5, ("fused grads vs composed", rel)
    lg = make_grad(fused_fn("graph"), quad=False)(Y, WO, WI, XR)
    lk = make_grad(fused_fn("kernel"), quad=False)(Y, WO, WI, XR)
    for a, b in zip(lg, lk):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            "fused grads differ across backends"
    print("OK fused", W)
""")


@pytest.mark.parametrize("world", [2, 4, 8])
def test_fused_boundary_matches_composed_pair_and_grads(world):
    out = run_devices(FUSED.replace("__WORLD__", str(world)), devices=world,
                      timeout=1200)
    assert "OK" in out


# ---------------------------------------------------------------------------
# OverlapPolicy resolution (single device, registry-backed)
# ---------------------------------------------------------------------------


def test_policy_single_resolution_point():
    from repro import hw, ops

    pol = ops.OverlapPolicy(mode="ring", backend="kernel",
                            ag_chunks=2, rs_chunks=3)
    r = pol.resolve("ag_matmul")
    assert r == ops.ResolvedOverlap("ring", "kernel", 2)
    # chunk count picked by registry kind (rs ops use the rs knob)
    assert pol.resolve("matmul_rs").chunks == 3
    # mode clamped by the registry: a2a_ep has no ring transport
    assert pol.resolve("a2a_ep").mode == "one_shot"
    # backend degraded off kernel-incapable pairs (bidir ag_matmul is
    # kernel-capable since the bidir_ring_ag protocol; moe_rs/bidir
    # still degrades). ring_attention is kernel-capable since the
    # carry-passing ring_fold protocol — no engine-internal degrade left.
    assert pol.with_modes(ag_matmul="bidir").resolve("ag_matmul").backend == \
        "kernel"
    assert pol.with_modes(moe_rs="bidir").resolve("moe_rs").backend == "graph"
    assert pol.resolve("ring_attention").backend == "kernel"
    assert pol.resolve("ag_matmul_2level") == ops.ResolvedOverlap(
        "two_level", "kernel", 2)
    # hw-aware degrade: no ICI links -> no remote-DMA engine -> graph
    no_ici = dataclasses.replace(hw.TARGET, ici_links=0)
    assert pol.resolve("ag_matmul", hw=no_ici).backend == "graph"
    assert pol.resolve("ag_matmul", hw=hw.TARGET).backend == "kernel"
    # dict ergonomics + describe
    pol2 = ops.OverlapPolicy(modes={"ag_matmul": "one_shot"})
    assert pol2.mode_for("ag_matmul") == "one_shot"
    assert pol2.describe("ag_matmul") == "one_shot/graph"


def test_policy_shape_keyed_layer_rules():
    from repro import ops

    pol = ops.OverlapPolicy(mode="ring")
    # the fused boundary op defaults OFF (mode "none") until opted in
    assert pol.mode_for("matmul_rs_ag_matmul") == "none"
    shape = ((512, 1024), (1024, 4096))
    pol = pol.with_layer("ag_matmul", shape, mode="one_shot", chunks=4)
    # the layer rule wins at ITS shape only; base resolution elsewhere
    r = pol.resolve("ag_matmul", shape=shape)
    assert (r.mode, r.chunks) == ("one_shot", 4)
    assert pol.resolve("ag_matmul", shape=((256, 1024), (1024, 4096))).mode \
        == "ring"
    assert pol.resolve("ag_matmul").mode == "ring"
    # shape keys flatten: list/tuple/int spellings hit the same rule
    assert ops.shape_key([512, 1024, 1024, 4096]) == \
        ops.shape_key(((512, 1024), (1024, 4096)))
    # layer overrides are re-clamped by the registry (a2a has no ring)
    pol2 = ops.OverlapPolicy().with_layer("a2a_ep", (8,), mode="ring")
    assert pol2.resolve("a2a_ep", shape=(8,)).mode == "one_shot"
    # JSON round-trip preserves base knobs AND layer rules
    back = ops.OverlapPolicy.from_json(pol.to_json())
    assert back == pol
    assert back.resolve("ag_matmul", shape=shape).chunks == 4


def test_parallel_config_carries_policy():
    from repro import ops
    from repro.configs.base import ParallelConfig

    # legacy fields fold into an equivalent policy on the fly
    legacy = ParallelConfig(tp=4, overlap_mode="one_shot", ag_chunks=2)
    explicit = ParallelConfig(
        tp=4, overlap=ops.OverlapPolicy(mode="one_shot", ag_chunks=2))
    for op in ("ag_matmul", "matmul_rs", "a2a_ep", "flash_decode"):
        assert legacy.policy.resolve(op) == explicit.policy.resolve(op), op
    # legacy fields AT their defaults are indistinguishable from unset:
    # the explicit policy simply wins
    both = ParallelConfig(tp=4, overlap_mode="ring",
                          overlap=ops.OverlapPolicy(mode="one_shot"))
    assert both.policy.resolve("ag_matmul").mode == "one_shot"


def test_declaration_validation_guards():
    """Declaration-time guards for backend-divergence hazards: a
    bidir_ring_ag binding needs a rowwise tile (the protocol tiles chunk
    HALVES), and a2a kernel protocols need tile=None (graph applies an
    a2a tile post-assembly, the protocol per landed block)."""
    from repro import ops

    with pytest.raises(ValueError, match="rowwise"):
        ops.OverlapOp(name="bad_bidir", kind="ag", tile=None,
                      transports=("ring", "bidir"),
                      kernel_protocols=(("bidir", "bidir_ring_ag"),))
    with pytest.raises(ValueError, match="tile=None"):
        ops.OverlapOp(name="bad_a2a", kind="a2a", tile=lambda x: 2 * x,
                      transports=("one_shot",), baseline="xla",
                      default="one_shot",
                      kernel_protocols=(("one_shot", "one_shot_a2a"),))


def test_conflicting_policy_and_legacy_fields_raise():
    """An explicit ``overlap`` policy plus NON-default legacy overlap
    fields is two sources of truth — a clear ValueError, not a silent
    preference (both argument orders)."""
    from repro import ops
    from repro.configs.base import ParallelConfig

    pol = ops.OverlapPolicy(mode="one_shot")
    with pytest.raises(ValueError, match="overlap_mode"):
        ParallelConfig(tp=4, overlap=pol, overlap_mode="bidir")
    with pytest.raises(ValueError, match="overlap_mode"):
        ParallelConfig(tp=4, overlap_mode="bidir", overlap=pol)
    # every legacy knob participates in the conflict check
    with pytest.raises(ValueError, match="ag_chunks"):
        ParallelConfig(tp=4, overlap=pol, ag_chunks=2)
    with pytest.raises(ValueError, match="overlap_backend"):
        ParallelConfig(tp=4, overlap_backend="kernel", overlap=pol)
    with pytest.raises(ValueError, match="overlap_modes"):
        ParallelConfig(tp=4, overlap=pol,
                       overlap_modes={"ag_matmul": "one_shot"})
    # non-overlap fields never conflict; policy-only configs are fine
    ParallelConfig(tp=4, overlap=pol, remat="none", moe_chunks=2)


def test_tuner_policy_feeds_default_pcfg_without_repacking():
    from repro import ops
    from repro.configs import ARCHS, reduced
    from repro.configs.shapes import SHAPES
    from repro.launch.steps import default_pcfg

    cfg = reduced(ARCHS["granite-3-2b"])
    shape = SHAPES["train_4k"]
    pcfg = default_pcfg(cfg, shape, multi_pod=False, overlap_mode="auto")
    assert isinstance(pcfg.overlap, ops.OverlapPolicy)
    # the tuner's policy resolves every registry op without error and the
    # CPU host recommendation is the graph backend
    r = pcfg.policy.resolve("ag_matmul")
    assert r.backend == "graph"
    assert r.chunks >= 1
    # explicit per-op pairs still win over the tuner's picks
    pcfg2 = default_pcfg(cfg, shape, multi_pod=False, overlap_mode="auto",
                         overlap_modes=(("ag_matmul", "ring"),))
    assert pcfg2.policy.resolve("ag_matmul").mode == "ring"
