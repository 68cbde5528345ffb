"""Autotuner tests: analytic model sanity + the paper's whole-step
empirical protocol (§3.8)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core import tuner
from repro import hw


def test_analytic_ag_prefers_overlap_when_compute_bound():
    # huge n_loc -> dot dominates -> any overlapped mode beats "none"
    choice = tuner.analytic_ag_matmul(4096, 8192, 8192, world=16)
    assert choice.mode != "none"
    assert choice.t_total < choice.t_comm + choice.t_compute


def test_analytic_ag_small_message_prefers_one_shot():
    # tiny per-step compute, tiny message: latency regime
    choice = tuner.analytic_ag_matmul(8, 256, 64, world=16)
    assert choice.mode in ("one_shot", "bidir")


def test_analytic_rs_overlap_wins_when_balanced():
    c = tuner.analytic_matmul_rs(4096, 2048, 8192, world=16)
    # comm-heavy regime: a ring transport (uni- or bidirectional) beats
    # the serialized baseline and the bandwidth-hungry one_shot
    assert c.mode in ("ring", "bidir")
    assert c.t_total <= c.t_compute + c.t_comm + 1e-9


def test_analytic_candidates_come_from_registry():
    from repro.core import overlap

    # every transport the registry declares is considered (plus baseline)
    assert set(overlap.transports_for("ag_matmul", include_baseline=True)) == {
        "none", "ring", "bidir", "one_shot"}
    assert set(overlap.transports_for("matmul_rs", include_baseline=True)) == {
        "none", "ring", "bidir", "one_shot"}
    # an op-restricted candidate list narrows the search
    only_ring = tuner.analytic_matmul_rs(4096, 2048, 8192, world=16,
                                         candidates=("ring",))
    assert only_ring.mode == "ring"


def test_recommend_overlap_modes_returns_policy():
    from repro import ops
    from repro.core import overlap

    rec = tuner.recommend_overlap_modes(4096, 8192, 8192, world=16)
    # the recommendation IS an OverlapPolicy — consumable by
    # ParallelConfig.overlap / repro.ops calls with no dict re-packing
    assert isinstance(rec, ops.OverlapPolicy)
    assert rec.mode_for("ag_matmul") in overlap.transports_for(
        "ag_matmul", include_baseline=True)
    assert rec.mode_for("matmul_rs") in overlap.transports_for(
        "matmul_rs", include_baseline=True)
    assert rec.resolve("ag_matmul").chunks >= 1
    assert rec.resolve("matmul_rs").chunks >= 1
    assert rec.backend in overlap.BACKENDS
    # CPU test host: the emulated-DMA kernel backend is a correctness
    # vehicle, not a fast path — the tuner must recommend graph here
    assert rec.resolve("ag_matmul").backend == "graph"
    # latency-bound ops keep their one-shot defaults in the policy map
    assert rec.mode_for("a2a_ep") == "one_shot"
    assert rec.mode_for("flash_decode") == "one_shot"
    # the carry-passing / compound-mesh ops enumerate too: ring attention
    # follows the AG regime pick (clamped to its transports) and the
    # 2-level ops resolve to their single two_level transport
    assert rec.mode_for("ring_attention") in overlap.transports_for(
        "ring_attention")
    assert rec.mode_for("ag_matmul_2level") == "two_level"
    assert rec.mode_for("matmul_rs_2level") == "two_level"


def test_analytic_ring_attention_placement():
    # causal at world >= 4: the balanced owner map halves the lockstep
    # critical path (contiguous charges the last rank ~the dense block
    # count), so zigzag is the interior optimum — not a tie broken by
    # enumeration order
    for world in (4, 8):
        ch = tuner.analytic_ring_attention(256, 64, world, causal=True)
        assert ch.placement == "zigzag", ch
        contig = tuner.analytic_ring_attention(
            256, 64, world, causal=True, placements=("contiguous",))
        assert ch.t_total < contig.t_total
    # the charged fractions themselves: contiguous -> ~1 - 1/(2W),
    # zigzag/striped -> ~1/2, and zigzag <= striped (no +1/(2*s_loc) tail)
    fc = tuner.causal_flop_fraction("contiguous", 8, 256)
    fz = tuner.causal_flop_fraction("zigzag", 8, 256)
    fs = tuner.causal_flop_fraction("striped", 8, 256)
    assert abs(fc - (1 - 1 / 16)) < 1e-2
    assert abs(fz - 0.5) < 1e-2 and fz <= fs < fc
    # non-causal: placements are FLOP-identical -> contiguous is kept
    # (strict-< selection) and forcing zigzag changes nothing
    nc = tuner.analytic_ring_attention(256, 64, 8, causal=False)
    assert nc.placement == "contiguous"
    ncz = tuner.analytic_ring_attention(256, 64, 8, causal=False,
                                        placements=("zigzag",))
    assert nc.t_total == ncz.t_total
    # recommend_overlap_modes lands the pick as a policy placement entry,
    # clamped off ops that never declared placements
    rec = tuner.recommend_overlap_modes(4096, 8192, 8192, world=16)
    assert rec.resolve("ring_attention").placement == "zigzag"
    assert rec.resolve("ag_matmul").placement == "contiguous"


def test_recommend_backend_enumerates_registry():
    from repro.core import overlap

    # EVERY registry op exposes both backends to the tuner — the last
    # fwd-less engine entries (ring attention, the 2-level compound-mesh
    # ops) gained kernel lowerings via the carry-passing / two-axis
    # executor protocols, so there is no graph-only tail left
    for name in overlap.registry():
        assert overlap.backends_for(name) == ("graph", "kernel"), name
    # the newly kernel-capable bindings, by name
    assert overlap.get("ring_attention").kernel_transports == (
        "ring", "one_shot")
    assert overlap.get("ag_matmul_2level").kernel_transports == ("two_level",)
    assert overlap.get("matmul_rs_2level").kernel_transports == ("two_level",)


def test_analytic_rs_enumerates_sub_chunks():
    # n divisible by 4: the ring candidate set includes rs_chunks in
    # {1,2,4}; whatever wins must be one of them
    c = tuner.analytic_matmul_rs(4096, 2048, 8192, world=16, max_sub=4,
                                 candidates=("ring",))
    assert c.mode == "ring"
    assert c.chunks_per_rank in (1, 2, 4)


def test_analytic_respects_link_bandwidth():
    slow = hw.HardwareSpec("slow", 197e12, 819e9, 1e9, 1, 16 << 30, 128 << 20)
    fast = hw.HardwareSpec("fast", 197e12, 819e9, 400e9, 4, 16 << 30, 128 << 20)
    c_slow = tuner.analytic_ag_matmul(1024, 4096, 4096, 16, spec=slow)
    c_fast = tuner.analytic_ag_matmul(1024, 4096, 4096, 16, spec=fast)
    assert c_slow.t_total > c_fast.t_total


def test_peaks_keyed_by_device_kind(monkeypatch):
    """Peaks come from the device kind JAX reports; an unknown chip is an
    error, not v5e's numbers. A CPU host plans for the target chip."""
    assert hw.spec_for("TPU v5 lite") is hw.TPU_V5E
    with pytest.raises(ValueError, match="no peak rates"):
        hw.spec_for("TPU v99")
    assert hw.local_spec() is hw.TARGET

    class Chip:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    with pytest.raises(ValueError, match="TPU v99"):
        tuner.recommend_overlap_modes(4096, 2048, 8192, 4)


def test_empirical_tuner_whole_step_protocol():
    """The tuner times the whole wrapped step, resets between configs, and
    picks the global argmin."""
    calls = {"reset": 0}

    def make_step(cfg):
        import time

        def step():
            # coarse 60ms granularity: robust to single-core scheduling noise
            time.sleep(0.06 * cfg)
            return jnp.zeros(())

        return step

    def reset():
        calls["reset"] += 1

    res = tuner.tune(make_step, [3, 1, 2], reset=reset, warmup=1, iters=2)
    assert res.config == 1
    # reset after every execution (warmup + iters per config)
    assert calls["reset"] == 3 * (1 + 2)
    assert set(res.all_timings) == {"1", "2", "3"}


def test_tune_default_reset_clears_emulated_shmem_state():
    """On CPU the tuner's default reset is ``shmem.emulated.reset``:
    stale symmetric-heap / signal-slot state left by a kernel-backend
    candidate cannot leak into (skew or deadlock) the next timed one."""
    from repro.shmem import emulated as em

    assert tuner.default_reset() is em.reset  # CPU test host

    def make_step(cfg):
        return lambda: jnp.zeros(())

    # simulate an aborted kernel candidate's leftover world state
    em._worlds[(999, 12345)] = em._World()
    tuner.tune(make_step, [1, 2], warmup=0, iters=1)  # reset="auto"
    assert (999, 12345) not in em._worlds, "default reset did not run"

    # an explicit reset=None disables the between-candidates cleanup
    em._worlds[(998, 12345)] = em._World()
    try:
        tuner.tune(make_step, [1], reset=None, warmup=0, iters=1)
        assert (998, 12345) in em._worlds
    finally:
        em.reset()


def test_tune_record_stalls_attaches_summary_per_config():
    """``tune(record_stalls=True)`` traces each candidate's timed
    iterations and reduces them into a per-config Summary in
    ``TuneResult.stalls`` — drained BEFORE the between-iteration reset
    (which drops worlds AND trace buffers)."""
    import numpy as np

    from repro import obs
    from repro.shmem import emulated as em

    def make_step(cfg):
        key = (7700 + cfg, 0)

        def step():
            # host-side shmem traffic stands in for a kernel candidate:
            # the pre-satisfied wait records a stall span, the signal
            # records the wire-side event
            em._host_signal(key, "recv", np.int32(0), np.int32(0),
                            np.int32(1), np.int32(1))
            em._host_wait(key, "recv", np.int32(0), np.int32(0),
                          np.int32(1))
            return jnp.zeros(())

        return step

    assert not obs.enabled()
    res = tuner.tune(make_step, [1, 2], warmup=1, iters=2,
                     record_stalls=True)
    assert not obs.enabled(), "tune must restore the prior tracing state"
    assert set(res.stalls) == {"1", "2"}
    for cfg_repr, s in res.stalls.items():
        assert s.n_events > 0
        assert 0.0 <= s.overlap_efficiency <= 1.0
        assert s.labels["config"] == cfg_repr

    # record_stalls off (the default): no tracing, no stalls
    res2 = tuner.tune(make_step, [1], warmup=0, iters=1)
    assert res2.stalls == {}

def test_search_candidates_come_from_registry():
    from repro.core import overlap

    grid = tuner.search_candidates("ag_matmul", chunks=(1, 2))
    modes = {m for m, _, _, _ in grid}
    assert modes == set(overlap.transports_for("ag_matmul",
                                               include_baseline=True))
    # the chunk axis only where the transport pipelines; baseline and
    # one_shot stay x1
    assert all(n == 1 for m, _, n, _ in grid if m in ("none", "one_shot"))
    assert any(n == 2 for m, _, n, _ in grid if m == "ring")
    # pairs the registry would clamp away never appear
    assert all(overlap.resolve_backend("ag_matmul", b, m) == b
               for m, b, _, _ in grid)
    assert all(overlap.resolve_wire("ag_matmul", w, m) == w
               for m, _, _, w in grid)
    # the fused boundary declaration enrolls automatically
    fused = tuner.search_candidates("matmul_rs_ag_matmul", chunks=(1, 2))
    assert {m for m, _, _, _ in fused} == {"none", "ring", "one_shot"}
    assert ("ring", "kernel", 2, "f32") in fused


def test_search_caches_per_op_shape_world_hw(tmp_path):
    """The PR-9 acceptance contract: a second identical ``search``
    performs ZERO new timings (``SEARCH_TIMINGS`` pinned); the cache
    round-trips through JSON; the searched policy round-trips through
    JSON and resolves per layer shape."""
    from repro import ops
    from repro.core import overlap

    tuner.clear_search_cache()

    def make_step(shape, resolved):
        assert isinstance(resolved, ops.ResolvedOverlap)
        return lambda: jnp.zeros(())

    shapes = [((64, 128), (128, 256)), ((64, 256), (256, 64))]
    n_grid = len(tuner.search_candidates("ag_matmul"))
    t0 = tuner.SEARCH_TIMINGS
    pol = tuner.search(make_step, "ag_matmul", shapes, world=4,
                       reset=None, warmup=0, iters=1)
    n_first = tuner.SEARCH_TIMINGS - t0
    assert n_first == 2 * n_grid  # one timed iter per candidate per shape
    assert isinstance(pol, ops.OverlapPolicy)
    for shp in shapes:
        r = pol.resolve("ag_matmul", shape=shp)
        assert r.mode in overlap.transports_for("ag_matmul",
                                                include_baseline=True)
        assert r.chunks >= 1

    # second identical search: served from cache, ZERO new timings
    pol2 = tuner.search(make_step, "ag_matmul", shapes, world=4,
                        reset=None, warmup=0, iters=1)
    assert tuner.SEARCH_TIMINGS - t0 == n_first, "cache miss on identical key"
    assert pol2 == pol

    # a different world is a different site: times again
    tuner.search(make_step, "ag_matmul", shapes[:1], world=8,
                 reset=None, warmup=0, iters=1)
    assert tuner.SEARCH_TIMINGS - t0 == n_first + n_grid

    # cache JSON round-trip: reload, then zero new timings again
    path = tmp_path / "search_cache.json"
    tuner.save_search_cache(path)
    tuner.clear_search_cache()
    assert tuner.load_search_cache(path) == 3  # 2 shapes@w4 + 1 shape@w8
    t1 = tuner.SEARCH_TIMINGS
    pol3 = tuner.search(make_step, "ag_matmul", shapes, world=4,
                        reset=None, warmup=0, iters=1)
    assert tuner.SEARCH_TIMINGS == t1, "loaded cache did not serve"
    assert pol3 == pol

    # the searched policy itself ships as JSON and still resolves
    back = ops.OverlapPolicy.from_json(pol.to_json())
    assert back == pol
    assert back.resolve("ag_matmul", shape=shapes[0]) == \
        pol.resolve("ag_matmul", shape=shapes[0])
    tuner.clear_search_cache()
