"""The paged serving path's own marks: host spans with counters from
``PagedEngine.step`` read back from a profiler capture, and the named
scopes in the compiled HLO of both paged programs."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS, reduced
from repro.configs.base import ParallelConfig
from repro.serve.engine import Request

PCFG = ParallelConfig(dp=1, tp=1, fsdp=False, compute_dtype="float32",
                      param_dtype="float32", overlap_mode="none")
PROMPTS = [[11, 7, 23, 4, 19, 3, 8, 2, 5], [9, 8, 7], [1, 2, 3, 4, 5, 6],
           [13, 12]]
NEW_TOKENS = 4


def _engine(mesh):
    from repro.launch.serve import build_paged_engine
    from repro.serve import ServeConfig

    scfg = ServeConfig(batch=2, max_len=32, page_size=8, chunk=4,
                       token_budget=6)
    return build_paged_engine(reduced(ARCHS["granite-3-2b"]), PCFG, scfg,
                              mesh)


def _serve(eng):
    """Serve PROMPTS to the end; returns (tokens per request, the plan of
    each working step, the cached lengths each plan was made at)."""
    reqs = [Request(prompt=list(p), max_new_tokens=NEW_TOKENS)
            for p in PROMPTS]
    for r in reqs:
        assert eng.add(r)
    plans, lens = [], []
    plan = eng.sched.plan

    def recording_plan():
        plans.append(plan())
        lens.append(eng.kv.lens.copy())
        return plans[-1]

    eng.sched.plan = recording_plan
    while eng.step():
        pass
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs], plans, lens


@pytest.fixture(scope="module")
def served(one_device_mesh, tmp_path_factory):
    """One engine serving PROMPTS without the profiler, and another under
    it; the second's ``serve.*`` host events, read back from the capture."""
    from jax.profiler import ProfileData

    plain, _, _ = _serve(_engine(one_device_mesh))
    eng = _engine(one_device_mesh)
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(log_dir):
        traced, plans, lens = _serve(eng)
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                       for ev in line.events if ev.name.startswith("serve.")]
    events.sort(key=lambda e: e[1])
    return {"engine": eng, "plans": plans, "lens": lens, "events": events,
            "plain": plain, "traced": traced}


def _named(served, name):
    return [e for e in served["events"] if e[0] == name]


@pytest.mark.parametrize("span, count", [
    ("serve.step", lambda eng, plans: len(plans)),
    ("serve.schedule", lambda eng, plans: len(plans)),
    ("serve.prefill", lambda eng, plans: eng.metrics().steps_prefill),
    ("serve.decode", lambda eng, plans: eng.metrics().steps_decode),
], ids=["step", "schedule", "prefill", "decode"])
def test_one_span_per_engine_call(served, span, count):
    eng, plans = served["engine"], served["plans"]
    assert len(plans) == eng.metrics().steps  # every plan was a working step
    assert len(_named(served, span)) == count(eng, plans) > 0


def test_span_counters_are_the_engines_counts(served):
    plans = served["plans"]
    decode = _named(served, "serve.decode")
    assert [e[3]["slots"] for e in decode] == [
        len(p.decode) for p in plans if p.decode]
    assert all(e[3]["context"] > 0 for e in decode)
    prefill = _named(served, "serve.prefill")
    assert sum(e[3]["tokens"] for e in prefill) == sum(map(len, PROMPTS))
    assert all(e[3]["streams"] == 1 for e in prefill)
    steps = _named(served, "serve.step")
    assert [e[3]["slots"] for e in steps][0] == 2  # both slots admitted
    assert max(e[3]["queue"] for e in steps) == len(PROMPTS) - 2
    assert steps[-1][3]["queue"] == 0 and all(e[3]["pages"] > 0 for e in steps)


def test_decode_pages_counter_is_what_the_kernel_reads(served):
    """``pages`` on ``serve.decode``: per decoding slot, the pages that
    hold its cached tokens and the new one."""
    page = served["engine"].kv.page_size
    per_slot = [[-(-(int(lens[i]) + 1) // page) for i in p.decode]
                for p, lens in zip(served["plans"], served["lens"])
                if p.decode]
    got = [e[3]["pages"] for e in _named(served, "serve.decode")]
    assert got == [sum(n) for n in per_slot]
    assert max(map(max, per_slot)) > 1  # some slot reads past its first page


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("child, parent", [
    ("serve.schedule", "serve.step"),
    ("serve.prefill", "serve.step"),
    ("serve.decode", "serve.step"),
    ("serve.prefill.launch", "serve.prefill"),
    ("serve.prefill.fetch", "serve.prefill"),
    ("serve.prefill.sample", "serve.prefill"),
    ("serve.decode.launch", "serve.decode"),
    ("serve.decode.fetch", "serve.decode"),
    ("serve.decode.sample", "serve.decode"),
])
def test_spans_nest(served, child, parent):
    children, parents = _named(served, child), _named(served, parent)
    assert children
    assert all(sum(_inside(c, p) for p in parents) == 1 for c in children)


def test_greedy_tokens_are_the_same_under_the_profiler(served):
    assert served["traced"] == served["plain"]
    assert all(len(t) == NEW_TOKENS for t in served["plain"])


# ---------------------------------------------------------------------------
# scopes in the compiled programs


@pytest.fixture(scope="module")
def programs(one_device_mesh):
    """The compiled HLO text of both paged programs."""
    eng = _engine(one_device_mesh)
    b, p = eng.scfg.batch, eng.kv.pages_per_slot
    i32 = jnp.int32
    decode = eng.decode_fn.lower(
        eng.params, eng.pools, jnp.zeros((b, p), i32), jnp.zeros((b,), i32),
        jnp.zeros((b,), bool), jnp.zeros((b, 1), i32))
    prefill = eng.prefill_fn.lower(
        eng.params, eng.pools, jnp.zeros((1, p), i32), jnp.zeros((1,), i32),
        jnp.zeros((1,), i32), jnp.zeros((1, eng.scfg.chunk), i32))
    return {"decode": decode.compile().as_text(),
            "prefill": prefill.compile().as_text()}


ATTN = "layers/while/body/closed_call/{}attn/"
SCOPES = {
    "decode": ["embed/", "layers/while/body/", "logits/",
               ATTN.format("") + "qkv/", ATTN.format("") + "kv_write/",
               ATTN.format("") + "paged_gather/",
               ATTN.format("") + "flash_decode/", ATTN.format("") + "out/",
               "layers/while/body/closed_call/mlp/"],
    # the prefill body runs under jax.checkpoint (remat)
    "prefill": ["embed/", "layers/while/body/", "logits/",
                ATTN.format("checkpoint/") + "qkv/",
                ATTN.format("checkpoint/") + "kv_write/",
                ATTN.format("checkpoint/") + "paged_gather/",
                ATTN.format("checkpoint/") + "chunk_attend/",
                ATTN.format("checkpoint/") + "out/",
                "layers/while/body/closed_call/checkpoint/mlp/"],
}
MODULES = {"decode": "jit_paged_decode_step",
           "prefill": "jit_prefill_chunk_step"}


@pytest.mark.parametrize("kind, scope", [
    (kind, scope) for kind, scopes in SCOPES.items() for scope in scopes])
def test_program_carries_scope(programs, kind, scope):
    module = MODULES[kind]
    assert programs[kind].startswith(f"HloModule {module},")
    prefix = f"jit({module[len('jit_'):]})/"
    names = re.findall(r'op_name="([^"]*)"', programs[kind])
    assert any(n.startswith(prefix + scope) for n in names), scope


def test_scan_slicing_lies_outside_the_layer_body(programs):
    """The loop's own operations (per-layer slices of the stacked leaves,
    the write-back) carry ``layers/`` and no body scope: what the
    benchmark's layer_loop metric reads."""
    names = re.findall(r'op_name="([^"]*)"', programs["decode"])
    own = [n for n in names if "/layers/" in n and "closed_call" not in n]
    assert any(re.search(r"/layers/while/body/dynamic_(update_)?slice", n)
               for n in own)
    assert not any(re.search(r"/(attn|mlp|moe)/", n) for n in own)


def test_moe_blocks_carry_their_scope(one_device_mesh):
    from repro.models import build_model

    cfg = reduced(ARCHS["granite-moe-3b-a800m"])
    model = build_model(cfg, PCFG)
    params, _ = model.init(jax.random.PRNGKey(0), jnp.float32)
    shapes = model.paged_cache_shapes(4, 8, jnp.float32)
    pools = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    text = jax.jit(jax.shard_map(
        model.decode_step_paged_local, mesh=one_device_mesh,
        in_specs=jax.sharding.PartitionSpec(), out_specs=(
            jax.sharding.PartitionSpec(), jax.sharding.PartitionSpec()),
        check_vma=False)).lower(
        params, pools, jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32),
        jnp.ones((2,), bool), jnp.zeros((2, 1), jnp.int32)).compile().as_text()
    assert "layers/while/body/closed_call/moe/" in text
