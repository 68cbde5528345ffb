"""Serving engine tests (single device, tiny model)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS, reduced
from repro.configs.base import ParallelConfig
from repro.models import build_model
from repro.serve import PagedEngine, Request, ServeConfig

PCFG = ParallelConfig(dp=1, tp=1, fsdp=False, compute_dtype="float32",
                      param_dtype="float32", overlap_mode="none")
SCFG = ServeConfig(batch=2, max_len=32, page_size=8, chunk=4, token_budget=8)


@pytest.fixture(scope="module")
def paged(one_device_mesh):
    """A factory of fresh paged engines (empty pools, slots and metrics)
    over one build of the two programs, for reduced granite-3-2b."""
    from repro.launch.serve import build_paged_engine

    built = build_paged_engine(reduced(ARCHS["granite-3-2b"]), PCFG, SCFG,
                               one_device_mesh)

    def fresh():
        return PagedEngine(built.prefill_fn, built.decode_fn, built.params,
                           jax.tree.map(jnp.zeros_like, built.pools), SCFG)

    return fresh


def test_engine_completes_requests(paged):
    eng = paged()
    for i in range(3):
        eng.add(Request(prompt=[1, 2, 3], max_new_tokens=4))
    leftover = eng.run(max_steps=30)
    assert leftover == []


def test_greedy_decoding_is_deterministic(paged):
    outs = []
    for _ in range(2):
        eng = paged()
        r = Request(prompt=[5, 6, 7], max_new_tokens=5)
        eng.add(r)
        eng.run(max_steps=30)
        outs.append(tuple(r.out_tokens))
    assert outs[0] == outs[1]
    assert len(outs[0]) == 5


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


def test_engine_metrics_counters(paged):
    eng = paged()
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


def test_overlap_modes_report_wire_dtype():
    """Serve provenance carries the resolved wire dtype (PR-6 wire axis):
    always-explicit, f32 default and per-op overrides both visible, one
    row per op of each phase's program."""
    from repro.ops.policy import OverlapPolicy

    pcfg = ParallelConfig(dp=1, tp=1, fsdp=False, compute_dtype="float32",
                          param_dtype="float32",
                          overlap=OverlapPolicy(
                              mode="ring", wires=(("ag_matmul", "int8"),)))
    eng = PagedEngine(None, None, None, None, SCFG, pcfg=pcfg)
    modes = eng.overlap_modes()
    assert set(modes) == {"decode:a2a_ep", "decode:flash_decode",
                          "prefill:ag_matmul", "prefill:matmul_rs"}
    assert modes["prefill:ag_matmul"].endswith("/int8"), modes
    for row in ("prefill:matmul_rs", "decode:a2a_ep", "decode:flash_decode"):
        assert modes[row].endswith("/f32"), modes
    # mode/backend still lead the string
    for desc in modes.values():
        assert len(desc.split("/")) >= 3, desc


def test_overlap_modes_empty_without_pcfg():
    eng = PagedEngine(None, None, None, None, SCFG)
    assert eng.overlap_modes() == {}


# ---------------------------------------------------------------------------
# Metrics under contention (fake programs + fake clock -> hand-computed)
# ---------------------------------------------------------------------------


class _FakeClock:
    """perf_counter stub: returns 0, 1, 2, ... — one tick per call."""

    def __init__(self):
        self.t = -1

    def __call__(self):
        self.t += 1
        return float(self.t)


def _fake_engine(monkeypatch, batch, max_len):
    """A PagedEngine on the fake clock whose programs return all-zero
    logits (greedy token 0, never eos) in the paged signatures."""
    import repro.serve.engine as engine_mod

    monkeypatch.setattr(engine_mod.time, "perf_counter", _FakeClock())

    def prefill_fn(params, pools, table, starts, n_valid, tokens):
        return np.zeros((tokens.shape[0], 16), np.float32), pools

    def decode_fn(params, pools, table, lens, active, token):
        return np.zeros((token.shape[0], 16), np.float32), pools

    scfg = ServeConfig(batch=batch, max_len=max_len, page_size=8, chunk=4,
                       token_budget=8)
    return PagedEngine(prefill_fn, decode_fn, None, None, scfg)


def test_metrics_under_contention_hand_computed(monkeypatch):
    """3 requests on 2 slots, prompt 3 + 2 generated each, fake clock.

    Adds stamp arrivals t=0,1,2; each program call then reads one tick
    after its logits are fetched. One data shard, so at most one
    prefill chunk a step; a 3-token prompt is one chunk, whose logits
    carry the first token; in a step the prefill runs before the decode.

      step 1: admit r1->slot 0, r2->slot 1; r3 queues. prefill r1 (t=3,
              r1 first).                        queue 1, occupancy 2/2
      step 2: prefill r2 (t=4, r2 first); decode r1 (t=5, r1 done,
              slot 0 freed).                    queue 1, occupancy 2/2
      step 3: admit r3->slot 0. prefill r3 (t=6, r3 first); decode r2
              (t=7, r2 done).                   queue 0, occupancy 2/2
      step 4: decode r3 (t=8, r3 done).         queue 0, occupancy 1/2

      ttft r1 = 3-0, r2 = 4-1, r3 = 6-2  (r3's queue wait INCLUDED)
      tpot r1 = 5-3, r2 = 7-4, r3 = 8-6  (excludes the first token)
      queue samples  [1, 1, 0, 0]        -> mean 0.5, max 1
      occupancy      [1, 1, 1, .5]       -> mean 0.875
    """
    eng = _fake_engine(monkeypatch, batch=2, max_len=32)
    for _ in range(3):
        eng.add(Request(prompt=[1, 2, 3], max_new_tokens=2))
    assert eng.run(max_steps=50) == []
    m = eng.metrics()
    assert m.requests_completed == 3
    assert m.tokens_generated == 6
    assert m.steps == 4
    assert m.steps_prefill == 3 and m.steps_decode == 3
    assert m.ttft_mean_s == (3 + 3 + 4) / 3
    assert m.ttft_max_s == 4.0            # r3's queue wait is in its TTFT
    assert m.tpot_mean_s == (2 + 3 + 2) / 3
    assert m.queue_depth_mean == 0.5
    assert m.queue_depth_max == 1
    assert m.slot_occupancy_mean == 0.875


def test_truncation_flag_on_capacity(monkeypatch):
    """A request that reaches max_len mid-generation finishes with an
    explicit truncated flag (no silent stranding) and is counted.

    max_len 6, prompt 3, max_new 8: admission reserves min(3 + 8, 6) = 6
    tokens, so the slot may generate 6 - 3 = 3. Step 1 prefills the
    prompt (first token), steps 2 and 3 decode one token each; the
    third token reaches the budget short of max_new, so the request
    ends truncated after 3 steps."""
    eng = _fake_engine(monkeypatch, batch=1, max_len=6)
    req = Request(prompt=[1, 2, 3], max_new_tokens=8)
    eng.add(req)
    assert eng.run(max_steps=20) == []    # finishes despite the tight cache
    assert req.done and req.truncated
    assert len(req.out_tokens) == 3       # prompt 3 + 3 generated = max_len
    m = eng.metrics()
    assert m.steps == 3
    assert m.requests_truncated == 1
    assert m.requests_completed == 1


def test_untruncated_requests_keep_flag_clear(monkeypatch):
    """max_len 32 holds prompt 3 + max_new 4: one prefill step and three
    decode steps emit the 4 tokens, and the request ends on max_new."""
    eng = _fake_engine(monkeypatch, batch=1, max_len=32)
    req = Request(prompt=[1, 2, 3], max_new_tokens=4)
    eng.add(req)
    eng.run(max_steps=20)
    assert req.done and not req.truncated
    assert len(req.out_tokens) == 4
    assert eng.metrics().requests_truncated == 0


# ---------------------------------------------------------------------------
# Slot-reuse isolation (the PR-8 regression): a reused slot must produce
# bit-identical tokens to a fresh engine — stale KV fully masked out.
# ---------------------------------------------------------------------------


def test_slot_reuse_matches_fresh_engine_paged(paged):
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

    assert (probe_tokens(paged(), churn=True)
            == probe_tokens(paged(), churn=False))


@pytest.mark.parametrize("arch", ["granite-3-2b", "granite-moe-3b-a800m"])
def test_launch_serve_runs_to_completion(arch):
    """The serving CLI's ``run`` on one device: every request of the
    synthetic stream is served, through both the chunked-prefill and
    the paged-decode program."""
    import argparse

    from repro.launch import serve as serve_mod

    ns = argparse.Namespace(
        arch=arch, reduced=True, dp=1, tp=1, no_fsdp=False,
        dtype="float32", batch=2, max_len=32, requests=3, new_tokens=4,
        temperature=0.0)
    eng = serve_mod.run(ns)
    m = eng.metrics()
    assert eng.leftover() == []
    assert m.requests_completed == ns.requests, m
    assert m.tokens_generated == ns.requests * ns.new_tokens, m
    assert m.steps_prefill > 0 and m.steps_decode > 0, m


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
