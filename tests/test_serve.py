"""Serving engine tests (single device, tiny model)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.configs.base import ParallelConfig
from repro.models import build_model
from repro.serve.engine import Engine, Request

PCFG = ParallelConfig(dp=1, tp=1, fsdp=False, compute_dtype="float32",
                      param_dtype="float32", overlap_mode="none")


def _build(one_device_mesh, batch=2, s_max=32):
    cfg = reduced(ARCHS["granite-3-2b"])
    model = build_model(cfg, PCFG)
    params, pspecs = model.init(jax.random.PRNGKey(0), jnp.float32)
    caches = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          model.cache_shapes(batch, s_max, jnp.float32))
    cache_specs = jax.tree.map(lambda x: P(*([None] * x.ndim)), caches)
    step = jax.jit(jax.shard_map(
        lambda p, c, n, t: model.decode_step_local(p, c, n, t),
        mesh=one_device_mesh,
        in_specs=(pspecs, cache_specs, None, P(None, None)),
        out_specs=(P(None, None), cache_specs), check_vma=False))
    return cfg, params, caches, step


def test_engine_completes_requests(one_device_mesh):
    cfg, params, caches, step = _build(one_device_mesh)
    eng = Engine(step, params, caches, batch=2, max_len=32)
    for i in range(3):
        eng.add(Request(prompt=[1, 2, 3], max_new_tokens=4))
    leftover = eng.run(max_steps=30)
    assert leftover == []


def test_greedy_decoding_is_deterministic(one_device_mesh):
    cfg, params, caches0, step = _build(one_device_mesh)
    outs = []
    for _ in range(2):
        caches = jax.tree.map(jnp.copy, caches0)
        eng = Engine(step, params, caches, batch=2, max_len=32)
        r = Request(prompt=[5, 6, 7], max_new_tokens=5)
        eng.add(r)
        eng.run(max_steps=30)
        outs.append(tuple(r.out_tokens))
    assert outs[0] == outs[1]
    assert len(outs[0]) == 5


def test_prefill_with_cache_matches_decode_loop(one_device_mesh):
    """The batched prefill (one forward pass -> logits + KV caches) must
    agree with token-by-token decode ingestion, both for the prefill
    logits AND for the next decode step using the produced caches."""
    cfg = reduced(ARCHS["granite-3-2b"])
    model = build_model(cfg, PCFG)
    params, pspecs = model.init(jax.random.PRNGKey(0), jnp.float32)
    b, s, s_max = 2, 8, 32
    toks = np.random.RandomState(1).randint(1, cfg.vocab_size, (b, s + 1)).astype(np.int32)

    pre = jax.jit(jax.shard_map(
        lambda p, t: model.prefill_with_cache_local(p, t, s_max, None),
        mesh=one_device_mesh, in_specs=(pspecs, P(None, None)),
        out_specs=(P(None, None), {"attn": {"k": P(*([None] * 5)),
                                            "v": P(*([None] * 5))}}),
        check_vma=False))
    logits_pre, caches_pre = pre(params, jnp.asarray(toks[:, :s]))

    caches = jax.tree.map(lambda sh: jnp.zeros(sh.shape, sh.dtype),
                          model.cache_shapes(b, s_max, jnp.float32))
    cache_specs = jax.tree.map(lambda x: P(*([None] * x.ndim)), caches)
    step = jax.jit(jax.shard_map(
        lambda p, c, n, t: model.decode_step_local(p, c, n, t),
        mesh=one_device_mesh,
        in_specs=(pspecs, cache_specs, None, P(None, None)),
        out_specs=(P(None, None), cache_specs), check_vma=False))
    logits_loop = None
    for i in range(s):
        logits_loop, caches = step(params, caches, jnp.int32(i),
                                   jnp.asarray(toks[:, i:i + 1]))
    np.testing.assert_allclose(np.asarray(logits_pre), np.asarray(logits_loop),
                               atol=2e-3, rtol=2e-3)
    # continue one decode step from BOTH cache states -> same logits
    nxt = jnp.asarray(toks[:, s:s + 1])
    l1, _ = step(params, caches_pre, jnp.int32(s), nxt)
    l2, _ = step(params, caches, jnp.int32(s), nxt)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=2e-3, rtol=2e-3)


def test_decode_matches_prefill_logits(one_device_mesh):
    """Feeding tokens one-by-one through the decode step must produce the
    same last-token logits as the full prefill forward."""
    cfg = reduced(ARCHS["granite-3-2b"])
    model = build_model(cfg, PCFG)
    params, pspecs = model.init(jax.random.PRNGKey(0), jnp.float32)
    b, s = 1, 8
    toks = np.random.RandomState(0).randint(1, cfg.vocab_size, (b, s)).astype(np.int32)

    pre = jax.jit(jax.shard_map(
        lambda p, t: model.prefill_logits_local(p, t, None),
        mesh=one_device_mesh, in_specs=(pspecs, P(None, None)),
        out_specs=P(None, None), check_vma=False))
    want = np.asarray(pre(params, jnp.asarray(toks)))

    caches = jax.tree.map(lambda sh: jnp.zeros(sh.shape, sh.dtype),
                          model.cache_shapes(b, 32, jnp.float32))
    cache_specs = jax.tree.map(lambda x: P(*([None] * x.ndim)), caches)
    step = jax.jit(jax.shard_map(
        lambda p, c, n, t: model.decode_step_local(p, c, n, t),
        mesh=one_device_mesh,
        in_specs=(pspecs, cache_specs, None, P(None, None)),
        out_specs=(P(None, None), cache_specs), check_vma=False))
    logits = None
    for i in range(s):
        logits, caches = step(params, caches, jnp.int32(i), jnp.asarray(toks[:, i:i+1]))
    np.testing.assert_allclose(np.asarray(logits), want, atol=2e-3, rtol=2e-3)


def test_engine_metrics_counters(one_device_mesh):
    cfg, params, caches, step = _build(one_device_mesh)
    eng = Engine(step, params, caches, batch=2, max_len=32)
    for _ in range(3):  # 3 requests on 2 slots -> one queues
        eng.add(Request(prompt=[1, 2, 3], max_new_tokens=4))
    leftover = eng.run(max_steps=30)
    assert leftover == []
    m = eng.metrics()
    assert m.requests_completed == 3
    assert m.tokens_generated == 12           # 3 requests x 4 tokens
    assert m.steps > 0
    assert m.ttft_mean_s > 0.0
    assert m.ttft_max_s >= m.ttft_mean_s
    assert m.tpot_mean_s > 0.0
    assert m.queue_depth_max >= 1             # the third request queued
    assert 0.0 < m.slot_occupancy_mean <= 1.0
    assert "Metrics(" in str(m)


def test_overlap_modes_report_wire_dtype(one_device_mesh):
    """Serve provenance carries the resolved wire dtype (PR-6 wire axis):
    always-explicit, f32 default and per-op overrides both visible."""
    from repro.ops.policy import OverlapPolicy

    cfg, params, caches, step = _build(one_device_mesh)
    pcfg = ParallelConfig(dp=1, tp=1, fsdp=False, compute_dtype="float32",
                          param_dtype="float32",
                          overlap=OverlapPolicy(
                              mode="ring", wires=(("ag_matmul", "int8"),)))
    eng = Engine(step, params, caches, batch=2, max_len=32, pcfg=pcfg)
    modes = eng.overlap_modes()
    assert set(modes) == set(Engine.OVERLAP_OPS)
    assert modes["ag_matmul"].endswith("/int8"), modes
    for op in ("matmul_rs", "a2a_ep", "flash_decode"):
        assert modes[op].endswith("/f32"), modes
    # mode/backend still lead the string
    for desc in modes.values():
        assert len(desc.split("/")) >= 3, desc


def test_overlap_modes_empty_without_pcfg(one_device_mesh):
    cfg, params, caches, step = _build(one_device_mesh)
    eng = Engine(step, params, caches, batch=2, max_len=32)
    assert eng.overlap_modes() == {}


# ---------------------------------------------------------------------------
# Metrics under contention (fake step fn + fake clock -> hand-computed)
# ---------------------------------------------------------------------------


class _FakeClock:
    """perf_counter stub: returns 0, 1, 2, ... — one tick per call."""

    def __init__(self):
        self.t = -1

    def __call__(self):
        self.t += 1
        return float(self.t)


def test_metrics_under_contention_hand_computed(monkeypatch):
    """3 requests on 2 slots, prompt 3 + 2 generated each, fake clock.

    Call order is deterministic: adds stamp t=0,1,2; each step stamps
    one tick (t=3..). A request takes 4 steps — the step feeding the
    last prompt token also yields the first generated token. Requests
    1+2 run steps 1-4 (now=3..6), request 3 queues through step 4 and
    runs steps 5-8 (now=7..10). Hand-computed:
      ttft r1 = 5-0, r2 = 5-1, r3 = 9-2  (queue wait INCLUDED)
      tpot    = 1 tick/token for all (excludes the first token)
      queue samples  [1]*4 + [0]*4   -> mean 0.5, max 1
      occupancy      [1.]*4 + [.5]*4 -> mean 0.75
    """
    import repro.serve.engine as engine_mod

    monkeypatch.setattr(engine_mod.time, "perf_counter", _FakeClock())
    step_fn = lambda p, c, n, t: (np.zeros((2, 16), np.float32), c)
    eng = Engine(step_fn, params=None, init_caches=None, batch=2, max_len=32)
    for _ in range(3):
        eng.add(Request(prompt=[1, 2, 3], max_new_tokens=2))
    assert eng.run(max_steps=50) == []
    m = eng.metrics()
    assert m.requests_completed == 3
    assert m.tokens_generated == 6
    assert m.steps == m.steps_decode == 8
    assert m.ttft_mean_s == (5 + 4 + 7) / 3
    assert m.ttft_max_s == 7.0            # r3's queue wait is in its TTFT
    assert m.tpot_mean_s == 1.0           # (t_done-t_first)/(n_out-1)
    assert m.queue_depth_mean == 0.5
    assert m.queue_depth_max == 1
    assert m.slot_occupancy_mean == 0.75


def test_truncation_flag_on_capacity(monkeypatch):
    """A request that hits max_len mid-generation finishes with an
    explicit truncated flag (no silent stranding) and is counted."""
    import repro.serve.engine as engine_mod

    monkeypatch.setattr(engine_mod.time, "perf_counter", _FakeClock())
    step_fn = lambda p, c, n, t: (np.zeros((1, 16), np.float32), c)
    eng = Engine(step_fn, params=None, init_caches=None, batch=1, max_len=4)
    req = Request(prompt=[1, 2, 3], max_new_tokens=8)
    eng.add(req)
    assert eng.run(max_steps=20) == []    # finishes despite the tight cache
    assert req.done and req.truncated
    assert len(req.out_tokens) == 2       # positions 3,4 then capacity
    m = eng.metrics()
    assert m.requests_truncated == 1
    assert m.requests_completed == 1


def test_untruncated_requests_keep_flag_clear(monkeypatch):
    import repro.serve.engine as engine_mod

    monkeypatch.setattr(engine_mod.time, "perf_counter", _FakeClock())
    step_fn = lambda p, c, n, t: (np.zeros((1, 16), np.float32), c)
    eng = Engine(step_fn, params=None, init_caches=None, batch=1, max_len=32)
    req = Request(prompt=[1, 2, 3], max_new_tokens=4)
    eng.add(req)
    eng.run(max_steps=20)
    assert req.done and not req.truncated
    assert eng.metrics().requests_truncated == 0


# ---------------------------------------------------------------------------
# Slot-reuse isolation (the PR-8 regression): a reused slot must produce
# bit-identical tokens to a fresh engine — stale KV fully masked out.
# ---------------------------------------------------------------------------


def test_slot_reuse_matches_fresh_engine_tokenwise(one_device_mesh):
    cfg, params, caches0, step = _build(one_device_mesh)
    probe_prompt = [11, 7, 23, 4]

    reused = Engine(step, params, jax.tree.map(jnp.copy, caches0),
                    batch=2, max_len=32)
    for _ in range(3):  # churn: fill + free both slots first
        reused.add(Request(prompt=[9, 8, 7, 6, 5], max_new_tokens=6))
    assert reused.run(max_steps=60) == []
    probe_a = Request(prompt=list(probe_prompt), max_new_tokens=5)
    reused.add(probe_a)
    assert reused.run(max_steps=60) == []

    fresh = Engine(step, params, jax.tree.map(jnp.copy, caches0),
                   batch=2, max_len=32)
    probe_b = Request(prompt=list(probe_prompt), max_new_tokens=5)
    fresh.add(probe_b)
    assert fresh.run(max_steps=60) == []
    assert probe_a.out_tokens == probe_b.out_tokens  # bit-identical


def test_slot_reuse_matches_fresh_engine_paged(one_device_mesh):
    from repro.launch.serve import build_paged_engine
    from repro.serve import ServeConfig

    cfg = reduced(ARCHS["granite-3-2b"])
    scfg = ServeConfig(batch=2, max_len=32, page_size=8, chunk=4,
                       token_budget=8)
    probe_prompt = [11, 7, 23, 4, 19, 3]

    def probe_tokens(engine, churn: bool):
        if churn:
            for _ in range(3):
                engine.add(Request(prompt=[9, 8, 7, 6, 5], max_new_tokens=6))
            assert engine.run() == []
        probe = Request(prompt=list(probe_prompt), max_new_tokens=5)
        engine.add(probe)
        assert engine.run() == []
        return probe.out_tokens

    reused = build_paged_engine(cfg, PCFG, scfg, one_device_mesh)
    fresh = build_paged_engine(cfg, PCFG, scfg, one_device_mesh)
    assert probe_tokens(reused, churn=True) == probe_tokens(fresh, churn=False)


def test_paged_decode_step_kernel_matches_reference_path(one_device_mesh,
                                                         monkeypatch):
    """One paged decode step on the Pallas path (the paged flash-decode
    kernel, interpreted) against the reference path (whole-table gather
    and jnp attention): the same params, pools, scattered block tables,
    live lanes and an idle one. The logits agree. The first layer's pools
    agree bit for bit, as the K/V write is the same on both paths; a later
    layer writes K/V computed from the attention output below it, so its
    new entries agree to rounding."""
    from repro.configs.base import ShapeConfig
    from repro.kernels import ops as kops
    from repro.launch.steps import build_paged_decode_step

    cfg = reduced(ARCHS["granite-3-2b"])
    batch, page, per_slot, n_pages = 4, 8, 4, 20

    def build():
        return build_paged_decode_step(
            cfg, PCFG, ShapeConfig("serve", seq_len=page * per_slot,
                                   global_batch=batch, kind="decode"),
            one_device_mesh, num_pages=n_pages, page_size=page,
            pages_per_slot=per_slot, cache_dtype=jnp.float32)

    ref_step, kernel_step = build(), build()
    params, _ = ref_step.model.init(jax.random.PRNGKey(0), jnp.float32)
    rs = np.random.RandomState(1)
    pools = jax.tree.map(lambda s: np.asarray(rs.randn(*s.shape), s.dtype),
                         ref_step.in_shapes[1])
    lens = np.asarray([0, 5, 8, 20], np.int32)  # cached; the new token next
    active = np.asarray([True, False, True, True])
    table = np.zeros((batch, per_slot), np.int32)
    ids = 1 + rs.permutation(n_pages - 1)
    at = 0
    for i, n in enumerate(lens // page + 1):
        table[i, :n] = ids[at:at + n]
        at += n
    token = rs.randint(1, cfg.vocab_size, (batch, 1)).astype(np.int32)
    args = (jnp.asarray(table), jnp.asarray(lens), jnp.asarray(active),
            jnp.asarray(token))

    def run(step):
        step_args = (params, jax.tree.map(jnp.asarray, pools), *args)
        kernel = "pallas_call" in str(jax.make_jaxpr(step.fn)(*step_args))
        return kernel, *step.fn(*step_args)

    kernel, want_logits, want_pools = run(ref_step)
    assert not kernel
    monkeypatch.setattr(kops, "use_pallas",
                        lambda force=None: force != "ref")
    kernel, got_logits, got_pools = run(kernel_step)
    assert kernel
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), atol=1e-4, rtol=1e-4)
    for got, want in zip(jax.tree.leaves(got_pools),
                         jax.tree.leaves(want_pools)):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("layer", [0, 1, 2])
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_write_changes_only_the_layers_touched_rows(kind, layer):
    """The K/V write into pools stacked over three layers, pages of 16
    tokens stored two to a 128-lane row: a decode write (one token a
    slot, an idle lane among them) and a 256-token prefill chunk that
    starts mid-page, carries 250 tokens and straddles 16 pages. Exactly
    the written tokens' lanes of layer ``layer`` change; every other
    layer, page and row, scratch page 0 included, stays bit for bit. The
    idle lane's table row lists a live lane's pages, as a freed slot's
    stale row may: it must write nowhere but scratch page 0, or its
    unchanged copy of the page could land over the live lane's token."""
    from repro.kernels.flash_decode import page_rows
    from repro.models.blocks import _write_pages

    rs = np.random.RandomState(layer)
    n_layers, n_pages, h, hd, ps = 3, 48, 2, 64, 16
    rows, width = page_rows(ps, hd)
    parts = width // hd
    pool = rs.randn(n_layers, n_pages, h, rows, width).astype(np.float32)
    if kind == "decode":  # lane 2 idle
        start, n_valid, c = np.array([0, 31, 17, 40]), np.array([1, 1, 0, 1]), 1
        per_stream = 4
    else:
        start, n_valid, c = np.array([37]), np.array([250]), 256
        per_stream = 20
    ids = 1 + rs.permutation(n_pages - 1)[:len(start) * per_stream]
    table = ids.reshape(len(start), per_stream).astype(np.int32)
    if kind == "decode":  # the idle lane's row still lists lane 1's pages
        table[2] = table[1]
    x = rs.randn(len(start), c, h, hd).astype(np.float32)

    want = pool.copy()
    for s in range(len(start)):
        for t in range(n_valid[s]):
            pos = start[s] + t
            r, p = divmod(pos % ps, parts)
            want[layer, table[s, pos // ps], :, r,
                 p * hd:(p + 1) * hd] = x[s, t]
    got = _write_pages(jnp.asarray(pool), jnp.int32(layer),
                       jnp.asarray(table), jnp.asarray(start, jnp.int32),
                       jnp.asarray(n_valid, jnp.int32), jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# Scheduler: deterministic planning + bounded-queue backpressure
# ---------------------------------------------------------------------------


def test_scheduler_plan_is_deterministic():
    from repro.serve import PagedKVCache, ServeConfig
    from repro.serve.scheduler import Scheduler

    scfg = ServeConfig(batch=4, max_len=16, page_size=8, chunk=4,
                       token_budget=6)
    kv = PagedKVCache(batch=4, max_len=16, page_size=8, dp_shards=2)
    sched = Scheduler(scfg, kv, dp_shards=2)
    for _ in range(3):
        sched.submit(Request(prompt=list(range(1, 7)), max_new_tokens=2))
    assert sched.admit() == [0, 1, 2]
    # one chunk per DP shard; slot 2's 4 tokens exceed the remaining
    # budget (6-4=2) so shard 1 waits this step
    assert sched.plan().prefill == [(0, 0, 4)]
    assert sched.note_chunk(0, 4) is False
    # next step: slot 0's 2-token tail + shard 1's first chunk both fit
    assert sched.plan().prefill == [(0, 4, 2), (2, 0, 4)]
    assert sched.note_chunk(0, 2) is True   # prompt done -> decode phase
    plan = sched.plan()
    assert plan.decode == [0]
    # decode consumed 1 budget token; slot 1's chunk (4) fits the
    # remaining 5, slot 2's tail (2) no longer does
    assert plan.prefill == [(1, 0, 4)]


def test_bounded_queue_backpressure():
    from repro.serve import PagedKVCache, ServeConfig
    from repro.serve.scheduler import Scheduler

    scfg = ServeConfig(batch=1, max_len=16, page_size=8, queue_cap=2)
    kv = PagedKVCache(batch=1, max_len=16, page_size=8)
    sched = Scheduler(scfg, kv)
    assert sched.submit(Request(prompt=[1]))
    assert sched.submit(Request(prompt=[2]))
    assert not sched.submit(Request(prompt=[3]))  # queue full
    assert sched.queue_depth() == 2
