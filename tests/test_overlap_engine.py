"""Ring-pipeline engine property tests.

1. Baseline equivalence: EVERY op in the engine registry
   (core/overlap.py), under EVERY transport it declares, must match its
   monolithic baseline numerically on world in {2, 4, 8} virtual
   devices. The script asserts its own coverage against the live
   registry, so registering a new op without extending the harness
   fails loudly.
2. Kernel-backend equivalence: every (op, transport) pair with a
   registered kernel lowering (OverlapSpec.kernel_transports) must match
   the graph backend's output — on CPU this runs the fused shmem kernels
   on the emulated-DMA backend (real put/signal/credit protocol).
3. Schedule validity: the bidir and 2-level orders in core/schedules.py
   satisfy their permutation / arrival / hand-off invariants.
"""
import textwrap

import pytest

from conftest import run_devices
from repro.core import schedules as S

SCRIPT = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro.core import overlap as ov
    from repro.core import collective_matmul as cm
    from repro.core import moe_overlap as mo
    from repro.core import flash_decode as fdm
    from repro.core.ring_attention import ring_attention
    from repro.kernels import ref

    W = __WORLD__
    TOL = 2e-4
    mesh = jax.make_mesh((W,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    tested = set()

    def sh(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    def check(name, got, want):
        err = np.abs(np.asarray(got) - np.asarray(want)).max()
        assert err < TOL, (name, err)

    # ---------------- ag_matmul / matmul_rs (1-level) ----------------
    M, K, N = 8 * W, 16, 4 * W
    A = jnp.asarray(rng.randn(M, K), jnp.float32)
    B = jnp.asarray(rng.randn(K, N), jnp.float32)
    wantAB = np.asarray(A) @ np.asarray(B)
    for mode in ov.transports_for("ag_matmul", include_baseline=True):
        f = sh(functools.partial(cm.ag_matmul, axis="tp", mode=mode,
                                 out_dtype=jnp.float32),
               (P("tp", None), P(None, "tp")), P(None, "tp"))
        check(("ag_matmul", mode), f(A, B), wantAB)
    f = sh(functools.partial(cm.ag_matmul, axis="tp", mode="ring",
                             chunks_per_rank=2, out_dtype=jnp.float32),
           (P("tp", None), P(None, "tp")), P(None, "tp"))
    check(("ag_matmul", "ring/sub2"), f(A, B), wantAB)
    tested.add("ag_matmul")

    A2 = jnp.asarray(rng.randn(M, 8 * W), jnp.float32)
    B2 = jnp.asarray(rng.randn(8 * W, N), jnp.float32)
    want2 = np.asarray(A2) @ np.asarray(B2)
    for mode in ov.transports_for("matmul_rs", include_baseline=True):
        f = sh(functools.partial(cm.matmul_rs, axis="tp", mode=mode,
                                 out_dtype=jnp.float32),
               (P(None, "tp"), P("tp", None)), P("tp", None))
        check(("matmul_rs", mode), f(A2, B2), want2)
    # sub-chunked RS ring (the rs_chunks knob, mirroring ag_chunks)
    f = sh(functools.partial(cm.matmul_rs, axis="tp", mode="ring",
                             chunks_per_rank=2, out_dtype=jnp.float32),
           (P(None, "tp"), P("tp", None)), P("tp", None))
    check(("matmul_rs", "ring/sub2"), f(A2, B2), want2)
    tested.add("matmul_rs")

    # ---------------- 2-level ops on a (2, W//2) compound mesh -------
    wo, wi = 2, max(1, W // 2)
    mesh2 = jax.make_mesh((wo, wi), ("pod", "tp"),
                          axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def sh2(fn, in_specs, out_specs):
        return jax.jit(jax.shard_map(fn, mesh=mesh2, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    AG2_SPECS = ((P(("pod", "tp"), None), P(None, ("pod", "tp"))),
                 P(None, ("pod", "tp")))
    RS2_SPECS = ((P(None, ("pod", "tp")), P(("pod", "tp"), None)),
                 P(("pod", "tp"), None))
    for mode in ov.transports_for("ag_matmul_2level", include_baseline=True):
        f = sh2(functools.partial(cm.ag_matmul_2level, inner_axis="tp",
                                  outer_axis="pod", mode=mode,
                                  out_dtype=jnp.float32), *AG2_SPECS)
        check(("ag_matmul_2level", mode), f(A, B), wantAB)
    tested.add("ag_matmul_2level")

    for mode in ov.transports_for("matmul_rs_2level", include_baseline=True):
        f = sh2(functools.partial(cm.matmul_rs_2level, inner_axis="tp",
                                  outer_axis="pod", mode=mode,
                                  out_dtype=jnp.float32), *RS2_SPECS)
        check(("matmul_rs_2level", mode), f(A2, B2), want2)
    tested.add("matmul_rs_2level")

    # ---------------- stand-alone gather / reduce-scatter ------------
    x = jnp.asarray(rng.randn(8 * W, 8), jnp.float32)
    for mode in ov.transports_for("all_gather", include_baseline=True):
        f = sh(functools.partial(cm.all_gather_chunked, axis="tp", mode=mode),
               P("tp", None), P(None, None))
        check(("all_gather", mode), f(x), np.asarray(x))
    tested.add("all_gather")

    for mode in ov.transports_for("reduce_scatter", include_baseline=True):
        f = sh(functools.partial(cm.reduce_scatter_chunked, axis="tp",
                                 mode=mode),
               P(None, None), P("tp", None))
        check(("reduce_scatter", mode), f(x), W * np.asarray(x))
    tested.add("reduce_scatter")

    # ---------------- MoE: ag_moe / moe_rs (rank-dependent expert) ---
    T_loc, D, E = 8, 8, 4
    xt = jnp.asarray(rng.randn(T_loc * W, D), jnp.float32)
    lt = jnp.asarray(rng.randn(T_loc * W, E), jnp.float32)
    We = jnp.asarray(rng.randn(D, D) / np.sqrt(D), jnp.float32)
    Wl = jnp.asarray(rng.randn(E, D), jnp.float32)

    def expert(tok, lg):
        # rowwise + rank-dependent (a d_ff-shard analogue): catches both
        # row misrouting and cross-rank misalignment
        me = lax.axis_index("tp").astype(jnp.float32)
        return jnp.tanh(tok @ We) * (1.0 + me) + lg @ Wl

    def ag_moe_err(xb, lb, mode):
        got = mo.ag_moe(xb, lb, expert, "tp", mode=mode)
        want = expert(lax.all_gather(xb, "tp", tiled=True),
                      lax.all_gather(lb, "tp", tiled=True))
        return lax.pmax(jnp.abs(got - want).max(), "tp")

    for mode in ov.transports_for("ag_moe", include_baseline=True):
        f = sh(functools.partial(ag_moe_err, mode=mode),
               (P("tp", None), P("tp", None)), P())
        assert float(f(xt, lt)) < TOL, ("ag_moe", mode, float(f(xt, lt)))
    tested.add("ag_moe")

    def moe_rs_err(xf, lf, mode):
        got = mo.moe_rs(xf, lf, expert, "tp", mode=mode)
        want = lax.psum_scatter(expert(xf, lf), "tp",
                                scatter_dimension=0, tiled=True)
        return lax.pmax(jnp.abs(got - want).max(), "tp")

    for mode in ov.transports_for("moe_rs", include_baseline=True):
        f = sh(functools.partial(moe_rs_err, mode=mode),
               (P(None, None), P(None, None)), P())
        assert float(f(xt, lt)) < TOL, ("moe_rs", mode)
    tested.add("moe_rs")

    # ---------------- EP AllToAll: one_shot vs XLA baseline ----------
    Eg, cap = 2 * W, 4
    xa = jnp.asarray(rng.randn(W * Eg, cap, D), jnp.float32)

    def a2a_pair(xb, mode):
        got = mo.a2a_ep(xb, "tp", mode=mode)
        rt = mo.a2a_ep_inverse(got, "tp", mode=mode)
        base = mo.a2a_ep(xb, "tp", mode="xla")
        return (lax.pmax(jnp.abs(got - base).max(), "tp"),
                lax.pmax(jnp.abs(rt - xb).max(), "tp"))

    for mode in ov.transports_for("a2a_ep", include_baseline=True):
        f = sh(functools.partial(a2a_pair, mode=mode),
               P("tp", None, None), (P(), P()))
        d_err, rt_err = f(xa)
        assert float(d_err) == 0.0 and float(rt_err) == 0.0, ("a2a_ep", mode)
    tested.add("a2a_ep")

    # ---------------- ring attention vs full-attention oracle --------
    Bb, H, HKV, Dh = 2, 4, 2, 16
    Sq = 8 * W
    q = jnp.asarray(rng.randn(Bb, H, Sq, Dh), jnp.float32)
    kk = jnp.asarray(rng.randn(Bb, HKV, Sq, Dh), jnp.float32)
    vv = jnp.asarray(rng.randn(Bb, HKV, Sq, Dh), jnp.float32)
    ATTN_SPECS = ((P(None, None, "tp", None),) * 3, P(None, None, "tp", None))
    for causal in (True, False):
        want_attn = np.asarray(ref.flash_attention(q, kk, vv, causal=causal))
        for mode in ov.transports_for("ring_attention", include_baseline=True):
            f = sh(functools.partial(ring_attention, axis="tp", causal=causal,
                                     mode=mode), *ATTN_SPECS)
            check(("ring_attention", mode, causal), f(q, kk, vv), want_attn)
    tested.add("ring_attention")

    # ---------------- flash-decode combine vs XLA gather -------------
    qd = jnp.asarray(rng.randn(Bb, H, Dh), jnp.float32)
    kd = jnp.asarray(rng.randn(Bb, HKV, 16 * W, Dh), jnp.float32)
    vd = jnp.asarray(rng.randn(Bb, HKV, 16 * W, Dh), jnp.float32)
    lens = jnp.full((Bb,), 16 * W, jnp.int32)
    want_dec, _ = ref.flash_decode(qd, kd, vd, length=lens)

    def ddecode(q_, k_, v_, mode, backend="graph"):
        ll = jnp.full((q_.shape[0],), k_.shape[2], jnp.int32)
        return fdm.distributed_flash_decode(q_, k_, v_, ll, "tp", mode=mode,
                                            backend=backend)

    for mode in ov.transports_for("flash_decode", include_baseline=True):
        f = sh(functools.partial(ddecode, mode=mode),
               (P(None,), P(None, None, "tp", None), P(None, None, "tp", None)),
               P(None,))
        check(("flash_decode", mode), f(qd, kd, vd), np.asarray(want_dec))
    tested.add("flash_decode")

    # ---------------- fused rs->ag boundary declaration --------------
    from repro import ops as oplib

    XRf = jnp.asarray(rng.randn(M, N), jnp.float32)
    WIf = jnp.asarray(rng.randn(N, 4 * W), jnp.float32)

    def seam(r, xr):
        # rank-local row fn at the boundary (residual add + nonlinearity)
        return jnp.tanh(r + xr)

    want_f = np.tanh(np.asarray(A2) @ np.asarray(B2) + np.asarray(XRf)) \
        @ np.asarray(WIf)
    FUSED_SPECS = ((P(None, "tp"), P("tp", None), P(None, "tp"),
                    P("tp", None)), P(None, "tp"))
    for mode in ov.transports_for("matmul_rs_ag_matmul",
                                  include_baseline=True):
        f = sh(functools.partial(oplib.matmul_rs_ag_matmul, axis="tp",
                                 mode=mode, out_dtype=jnp.float32, mid=seam),
               *FUSED_SPECS)
        check(("matmul_rs_ag_matmul", mode), f(A2, B2, WIf, XRf), want_f)
    # sub-chunked boundary (the chunks knob splits the reduced block)
    f = sh(functools.partial(oplib.matmul_rs_ag_matmul, axis="tp",
                             mode="ring", chunks=2, out_dtype=jnp.float32,
                             mid=seam), *FUSED_SPECS)
    check(("matmul_rs_ag_matmul", "ring/sub2"), f(A2, B2, WIf, XRf), want_f)
    tested.add("matmul_rs_ag_matmul")

    # ---------------- kernel backend: fused shmem kernels ------------
    # Every (op, transport) the registry declares kernel-capable must
    # match the graph backend's output (the emulated-DMA backend runs
    # the real put/signal/credit protocol on CPU virtual devices).
    def run_ag(mode, backend):
        f = sh(functools.partial(cm.ag_matmul, axis="tp", mode=mode,
                                 backend=backend, out_dtype=jnp.float32),
               (P("tp", None), P(None, "tp")), P(None, "tp"))
        return np.asarray(f(A, B))

    def run_rs(mode, backend):
        f = sh(functools.partial(cm.matmul_rs, axis="tp", mode=mode,
                                 backend=backend, out_dtype=jnp.float32),
               (P(None, "tp"), P("tp", None)), P("tp", None))
        return np.asarray(f(A2, B2))

    def run_gather(mode, backend):
        f = sh(functools.partial(cm.all_gather_chunked, axis="tp", mode=mode,
                                 backend=backend),
               P("tp", None), P(None, None))
        return np.asarray(f(x))

    def run_rsc(mode, backend):
        f = sh(functools.partial(cm.reduce_scatter_chunked, axis="tp",
                                 mode=mode, backend=backend),
               P(None, None), P("tp", None))
        return np.asarray(f(x))

    def run_a2a(mode, backend):
        # both directions under one runner: the inverse reuses the same
        # registered op with transposed block placement, on a DISPATCHED
        # (capacity-grouped) tensor
        f = sh(functools.partial(mo.a2a_ep, axis="tp", mode=mode,
                                 backend=backend),
               P("tp", None, None), P("tp", None, None))
        y = f(xa)
        g = sh(lambda yy: mo.a2a_ep_inverse(yy, "tp", mode=mode,
                                            backend=backend),
               P("tp", None, None), P("tp", None, None))
        return np.concatenate([np.asarray(y).ravel(),
                               np.asarray(g(y)).ravel()])

    def run_fd(mode, backend):
        f = sh(functools.partial(ddecode, mode=mode, backend=backend),
               (P(None,), P(None, None, "tp", None), P(None, None, "tp", None)),
               P(None,))
        return np.asarray(f(qd, kd, vd))

    def run_moe_rs(mode, backend):
        f = sh(lambda xf, lf: mo.moe_rs(xf, lf, expert, "tp", mode=mode,
                                        backend=backend),
               (P(None, None), P(None, None)), P("tp", None))
        return np.asarray(f(xt, lt))

    def run_rattn(mode, backend):
        # both causal regimes under one runner: the carry-passing
        # ring_fold protocol's owner swizzle feeds the causal mask
        outs = []
        for causal in (True, False):
            f = sh(functools.partial(ring_attention, axis="tp",
                                     causal=causal, mode=mode,
                                     backend=backend), *ATTN_SPECS)
            outs.append(np.asarray(f(q, kk, vv)).ravel())
        return np.concatenate(outs)

    def run_ag2(mode, backend):
        f = sh2(functools.partial(cm.ag_matmul_2level, inner_axis="tp",
                                  outer_axis="pod", mode=mode,
                                  backend=backend, out_dtype=jnp.float32),
                *AG2_SPECS)
        return np.asarray(f(A, B))

    def run_rs2(mode, backend):
        f = sh2(functools.partial(cm.matmul_rs_2level, inner_axis="tp",
                                  outer_axis="pod", mode=mode,
                                  backend=backend, out_dtype=jnp.float32),
                *RS2_SPECS)
        return np.asarray(f(A2, B2))

    def run_fused(mode, backend):
        f = sh(functools.partial(oplib.matmul_rs_ag_matmul, axis="tp",
                                 mode=mode, backend=backend,
                                 out_dtype=jnp.float32, mid=seam),
               *FUSED_SPECS)
        return np.asarray(f(A2, B2, WIf, XRf))

    kernel_runners = {"ag_matmul": run_ag, "matmul_rs": run_rs,
                      "all_gather": run_gather, "reduce_scatter": run_rsc,
                      "a2a_ep": run_a2a, "flash_decode": run_fd,
                      "moe_rs": run_moe_rs, "ring_attention": run_rattn,
                      "ag_matmul_2level": run_ag2,
                      "matmul_rs_2level": run_rs2,
                      "matmul_rs_ag_matmul": run_fused}
    kernel_pairs = [(nm, t) for nm, spec in ov.registry().items()
                    for t in spec.kernel_transports]
    assert kernel_pairs, "no kernel-capable (op, transport) pairs registered"
    for nm, t in kernel_pairs:
        if nm == "ag_moe":
            continue  # rank-dependent output: compared in-program below
        assert nm in kernel_runners, \
            f"kernel transport {nm}/{t} without a harness"
        got_k = kernel_runners[nm](t, "kernel")
        got_g = kernel_runners[nm](t, "graph")
        if nm in ("a2a_ep", "all_gather", "flash_decode"):
            # pure data movement: BIT-identical across backends
            assert np.array_equal(got_k, got_g), ("kernel-vs-graph", nm, t)
        else:
            err = np.abs(got_k - got_g).max()
            assert err < TOL, ("kernel-vs-graph", nm, t, err)
    # ag_moe's per-rank outputs differ by design (rank-dependent expert):
    # kernel-vs-graph is compared inside the SPMD program
    def agmoe_kernel_err(xb, lb, mode):
        got_k = mo.ag_moe(xb, lb, expert, "tp", mode=mode, backend="kernel")
        got_g = mo.ag_moe(xb, lb, expert, "tp", mode=mode, backend="graph")
        return lax.pmax(jnp.abs(got_k - got_g).max(), "tp")

    for mode in ov.get("ag_moe").kernel_transports:
        f = sh(functools.partial(agmoe_kernel_err, mode=mode),
               (P("tp", None), P("tp", None)), P())
        assert float(f(xt, lt)) < TOL, ("ag_moe kernel", mode)

    # mixed precision (bf16 tokens + f32 router logits): the packed
    # riding chunk must promote, not round — kernel == graph exactly
    # (exact pack/unpack casts; moe_rs partials ride and reduce in f32)
    xt16 = xt.astype(jnp.bfloat16)

    def expert16(tok, lg):
        assert tok.dtype == jnp.bfloat16 and lg.dtype == jnp.float32
        me = lax.axis_index("tp").astype(jnp.float32)
        t32 = tok.astype(jnp.float32)
        return jnp.tanh(t32 @ We) * (1.0 + me) + lg @ Wl

    def moe_rs16(xf, lf, backend):
        return mo.moe_rs(xf, lf, expert16, "tp", mode="ring",
                         backend=backend).astype(jnp.float32)

    k16 = np.asarray(sh(functools.partial(moe_rs16, backend="kernel"),
                        (P(None, None), P(None, None)), P("tp", None))(xt16, lt))
    g16 = np.asarray(sh(functools.partial(moe_rs16, backend="graph"),
                        (P(None, None), P(None, None)), P("tp", None))(xt16, lt))
    assert np.array_equal(k16, g16), "moe_rs mixed-precision kernel parity"

    def agmoe16_err(xb, lb):
        got_k = mo.ag_moe(xb, lb, expert16, "tp", mode="ring",
                          backend="kernel")
        got_g = mo.ag_moe(xb, lb, expert16, "tp", mode="ring",
                          backend="graph")
        return lax.pmax(jnp.abs(got_k - got_g).max(), "tp")

    assert float(sh(agmoe16_err, (P("tp", None), P("tp", None)),
                    P())(xt16, lt)) == 0.0, "ag_moe mixed-precision parity"
    # requesting kernel where no kernel lowering exists degrades to graph
    check(("matmul_rs", "bidir", "kernel->graph"),
          run_rs("bidir", "kernel"), want2)

    # grads are BIT-identical across backends (the kernel forward keeps
    # the graph-lowered dual as its backward through the ONE custom_vjp)
    def a2a_grad(backend):
        def loss(xb):
            out = mo.a2a_ep(xb, "tp", mode="one_shot", backend=backend)
            return lax.psum(jnp.sum(out * out), "tp")
        return np.asarray(sh(jax.grad(loss), P("tp", None, None),
                             P("tp", None, None))(xa))

    assert np.array_equal(a2a_grad("graph"), a2a_grad("kernel")), "a2a grads"

    packed = jnp.asarray(rng.randn(Bb, H, Dh + 1), jnp.float32)

    def fd_grad(backend):
        def loss(p):
            out = ov.dispatch("flash_decode", p, axis="tp", mode="one_shot",
                              backend=backend)
            return lax.psum(jnp.sum(out * out), "tp")
        return np.asarray(sh(jax.grad(loss), P(None, None, None),
                             P(None, None, None))(packed))

    assert np.array_equal(fd_grad("graph"), fd_grad("kernel")), "fd grads"

    def bidir_ag_grads(backend):
        def loss(a, b):
            out = cm.ag_matmul(a, b, "tp", mode="bidir", backend=backend,
                               out_dtype=jnp.float32)
            return lax.psum(jnp.sum(out * out), "tp")
        return [np.asarray(t) for t in
                sh(jax.grad(loss, argnums=(0, 1)),
                   (P("tp", None), P(None, "tp")),
                   (P("tp", None), P(None, "tp")))(A, B)]

    for a, b in zip(bidir_ag_grads("graph"), bidir_ag_grads("kernel")):
        assert np.array_equal(a, b), "bidir ag_matmul grads differ"

    # ring attention: grads BIT-identical across backends (the kernel's
    # ring_fold forward keeps the jax.vjp-through-the-fold-chain graph
    # dual through the ONE custom_vjp), causal AND non-causal — and the
    # ring forward is bit-equal too (same fold order, same f32 ops).
    def rattn_grads(backend, causal):
        def loss(q_, k_, v_):
            out = ring_attention(q_, k_, v_, "tp", causal=causal,
                                 mode="ring", backend=backend)
            return lax.psum(jnp.sum(out * out), "tp")
        return [np.asarray(t) for t in
                sh(jax.grad(loss, argnums=(0, 1, 2)),
                   ATTN_SPECS[0], (P(None, None, "tp", None),) * 3)(q, kk, vv)]

    for causal in (True, False):
        for a, b in zip(rattn_grads("graph", causal),
                        rattn_grads("kernel", causal)):
            assert np.array_equal(a, b), ("ring_attention grads", causal)

    # 2-level grads bit-identical across backends too
    def ag2_grads(backend):
        def loss(a, b):
            out = cm.ag_matmul_2level(a, b, "tp", "pod", backend=backend,
                                      out_dtype=jnp.float32)
            return lax.psum(jnp.sum(out * out), ("pod", "tp"))
        return [np.asarray(t) for t in
                sh2(jax.grad(loss, argnums=(0, 1)), AG2_SPECS[0],
                    AG2_SPECS[0])(A, B)]

    for a, b in zip(ag2_grads("graph"), ag2_grads("kernel")):
        assert np.array_equal(a, b), "ag_matmul_2level grads differ"

    # ---------------- coverage: no registered op left untested -------
    missing = set(ov.registry()) - tested
    assert not missing, f"registry ops without a baseline test: {missing}"
    print("OK", sorted(tested))
""")


@pytest.mark.parametrize("world", [2, 4, 8])
def test_registry_pipelines_match_baselines(world):
    out = run_devices(SCRIPT.replace("__WORLD__", str(world)), devices=world,
                      timeout=1200)
    assert "OK" in out


# ---------------------------------------------------------------------------
# Schedule validity for the bidir and 2-level orders
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("world", [3, 4, 8, 16, 17])
def test_bidir_ag_schedule_valid(world):
    assert S.validate_bidir_ag(world)


@pytest.mark.parametrize("world", [3, 4, 8, 16, 17])
def test_bidir_rs_schedule_valid(world):
    assert S.validate_bidir_rs(world)


@pytest.mark.parametrize("no,ni", [(2, 2), (2, 4), (4, 4), (3, 5)])
def test_two_level_schedules_valid(no, ni):
    assert S.validate_two_level_ag(no, ni)
    assert S.validate_two_level_rs(no, ni)


def test_registry_declares_known_transports_only():
    from repro.core import overlap as ov

    for name, spec in ov.registry().items():
        assert spec.transports, name
        for t in spec.transports:
            assert t in ov.TRANSPORTS, (name, t)
        assert spec.default in spec.transports, name
        # resolving an unsupported request falls back to the default
        assert ov.resolve_mode(name, "definitely-not-a-mode") == spec.default


def test_registry_backend_resolution():
    import pytest

    from repro.core import overlap as ov

    for name, spec in ov.registry().items():
        # kernel transports are a subset of the op's transports and come
        # paired with a kernel lowering
        for t in spec.kernel_transports:
            assert t in spec.transports, (name, t)
        assert bool(spec.kernel_transports) == (spec.kernel_fwd is not None)
        assert ov.backends_for(name)[0] == "graph"
        # graph always resolves; kernel resolves only for kernel pairs
        assert ov.resolve_backend(name, "graph") == "graph"
        for t in spec.transports:
            want = "kernel" if t in spec.kernel_transports else "graph"
            assert ov.resolve_backend(name, "kernel", t) == want, (name, t)
        # the baseline mode never lowers through the kernel backend
        assert ov.resolve_backend(name, "kernel", spec.baseline) == "graph"
    with pytest.raises(ValueError):
        ov.resolve_backend("ag_matmul", "definitely-not-a-backend")


def test_every_registry_op_is_dispatch_routed_and_kernel_capable():
    """No graph-only OR fwd-less escape hatches left: EVERY op in the
    engine registry routes through ``overlap.dispatch`` (a registered
    ``fwd``) and has a kernel lowering — including ring attention (the
    carry-passing ``ring_fold`` protocol) and the 2-level compound-mesh
    ops (the two-axis ``two_level_ag``/``two_level_rs`` protocols). The
    backend axis covers the whole registry."""
    from repro.core import overlap as ov

    registry = ov.registry()
    assert set(registry) >= {"ag_matmul", "matmul_rs", "all_gather",
                             "reduce_scatter", "a2a_ep", "flash_decode",
                             "ag_moe", "moe_rs", "ring_attention",
                             "ag_matmul_2level", "matmul_rs_2level"}
    for name, spec in registry.items():
        assert spec.fwd is not None, f"{name} is not dispatch-routed"
        assert ov.backends_for(name) == ("graph", "kernel"), name
    # this PR's named bindings, specifically
    assert ov.get("ring_attention").kernel_transports == ("ring", "one_shot")
    assert ov.get("ag_matmul_2level").kernel_transports == ("two_level",)
    assert ov.get("matmul_rs_2level").kernel_transports == ("two_level",)
    # the fused boundary declaration is registry-routed too: its kernel
    # transport binds the chained push_rs -> ring_ag protocol
    assert ov.get("matmul_rs_ag_matmul").kernel_transports == ("ring",)
    # earlier PRs' bindings stay
    assert "one_shot" in ov.get("a2a_ep").kernel_transports
    assert "one_shot" in ov.get("flash_decode").kernel_transports
    assert "bidir" in ov.get("ag_matmul").kernel_transports
    # ...and the fold ops differentiate: the kernel forward keeps the
    # jax.vjp-through-the-fold-chain dual via the shared custom_vjp
    assert ov.get("ring_attention").bwd is not None
    assert ov.get("ag_moe").bwd is not None and ov.get("moe_rs").bwd is not None


_SCAN_KERNEL_TRAIN = textwrap.dedent("""
    import functools
    import jax, jax.numpy as jnp, numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P
    from repro import ops

    W = 2
    mesh = jax.make_mesh((W,), ("tp",), axis_types=(jax.sharding.AxisType.Auto,))
    rng = np.random.RandomState(0)
    A = jnp.asarray(rng.randn(4 * W, 8), jnp.float32)
    Wt = jnp.asarray(rng.randn(8, 2 * W), jnp.float32)

    def loss(a, w, backend):
        # a 2-"layer" scan over the overlapped op: the whole-model
        # training shape (layers scanned, overlapped op inside)
        def layer(carry, _):
            y = ops.ag_matmul(carry, w, axis="tp", mode="ring",
                              backend=backend, out_dtype=jnp.float32)
            return carry, jnp.sum(y * y)
        _, ys = lax.scan(layer, a, jnp.arange(2))
        return lax.psum(jnp.sum(ys), "tp")

    def grads(backend):
        g = jax.jit(jax.shard_map(
            jax.grad(functools.partial(loss, backend=backend),
                     argnums=(0, 1)),
            mesh=mesh, in_specs=(P("tp", None), P(None, "tp")),
            out_specs=(P("tp", None), P(None, "tp")),
            check_vma=False))(A, Wt)
        return [np.asarray(x) for x in g]

    for gk, gg in zip(grads("kernel"), grads("graph")):
        np.testing.assert_array_equal(gk, gg)
    print("OK scan kernel train")
""")


def test_kernel_backend_training_under_scan_matches_graph():
    """Kernel-backend TRAINING under ``lax.scan`` (the emulated
    backend's io_callback effects inside the shared custom_vjp, layers
    scanned): the grads equal the graph backend's bit for bit."""
    out = run_devices(_SCAN_KERNEL_TRAIN, devices=2)
    assert "OK scan kernel train" in out
