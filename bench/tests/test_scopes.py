"""The readers of the program's named scopes (bench/scopes.py), on a
hand-made trace whose answers are known, and the accepted readers on
the recorded TPU trace, whose readings the program's marks must not
move."""
import json

import pytest
from jax.profiler import ProfileData

from bench import harness, kernels, peaks, readers, scopes, trace
from bench.tests.test_trace import F9, HOST, K, RECORDED, WHILE, _plane
from bench.tests.tiny import ROOT

# The window of test_trace.py with the serving engine's own spans inside
# the benchmark's, and programs named for themselves whose operations
# carry the program's scopes: in each decode run the kernel, the page
# gather (fusion.9) and the layer loop's own slice (dynamic-slice.4); in
# the prefill run the chunk attention (fusion.2).
SERVE_HOST = HOST + [
    ("serve.step", 11, 39), ("serve.decode", 11, 38),
    ("serve.decode.launch", 11, 15), ("serve.decode.fetch", 15, 36),
    ("serve.decode.sample", 36, 38),
    ("serve.step", 51, 88), ("serve.prefill", 51, 59),
    ("serve.prefill.launch", 51, 55), ("serve.prefill.fetch", 55, 58),
    ("serve.prefill.sample", 58, 59), ("serve.decode", 59, 87),
    ("serve.decode.launch", 59, 63), ("serve.decode.fetch", 63, 86),
    ("serve.decode.sample", 86, 87)]
MODULES = [("jit_paged_decode_step(11)", 15, 35),
           ("jit_prefill_chunk_step(22)", 55, 58),
           ("jit_paged_decode_step(11)", 63, 85)]
DS = "%dynamic-slice.4 = bf16[64,64]{1,0} dynamic-slice(%w, %i)"
OPS = [(WHILE, 15, 35), (K, 15, 22), (F9, 22, 30), (DS, 30, 35),
       ("%fusion.2 = bf16[1,256,64]{2,1,0} fusion(%d)", 55, 58),
       (K, 63, 68), (F9, 68, 80), (DS, 80, 85)]
BODY = "jit(paged_decode_step)/layers/while/body"
DECODE_HLO = f"""
HloModule jit_paged_decode_step
  %closed_call.3 = (f32[4,2,2,16]{{3,2,1,0}}, f32[4,2,2,128]{{3,2,1,0}}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/closed_call/attn/flash_decode/pallas_call"}}
  %fusion.9 = bf16[4,64]{{1,0}} fusion(%c), kind=kLoop, metadata={{op_name="{BODY}/closed_call/attn/paged_gather/gather"}}
  %dynamic-slice.4 = bf16[64,64]{{1,0}} dynamic-slice(%w, %i), metadata={{op_name="{BODY}/dynamic_slice"}}
"""
PREFILL_HLO = """
HloModule jit_prefill_chunk_step
  %fusion.2 = bf16[1,256,64]{2,1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(prefill_chunk_step)/layers/while/body/closed_call/checkpoint/attn/chunk_attend/dot_general"}
"""


def _reduce(tmp_path, host):
    text = "\n".join([
        _plane(1, "/device:TPU:0", [("XLA Modules", MODULES), ("XLA Ops", OPS)]),
        _plane(2, "/host:CPU", [("python", host)]),
    ])
    path = tmp_path / f"t{len(host)}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce(str(path))


@pytest.fixture
def served(tmp_path):
    return _reduce(tmp_path, SERVE_HOST)


def test_the_programs_spans_leave_the_accepted_reduction_as_it_was(
        served, tmp_path):
    bare = _reduce(tmp_path, HOST)
    assert served.host == bare.host
    assert [(r.kind, r.module, r.ops) for r in served.runs] == [
        (r.kind, r.module, r.ops) for r in bare.runs]
    assert [r.kind for r in served.runs] == ["decode", "prefill", "decode"]
    assert trace.idle_by_span(served) == trace.idle_by_span(bare)


def test_readers_of_the_programs_scopes(served):
    spec = {"head_dim": 16}
    ctx = harness.Context(
        spec=spec, records=[], window=None, window_s=0.1, setup_s=1.0,
        trace=served, calls={"decode": [[9, 19], [30]], "prefill": [(0, 7)]},
        programs={"decode": kernels.Program(DECODE_HLO, spec),
                  "prefill": kernels.Program(PREFILL_HLO, spec)})

    def read(name):
        return harness.load_reader(ROOT / "bench" / "metrics" / f"{name}.py")(ctx)

    for cell in ("itl", "tok"):
        assert read(f"paged_gather_ms.{cell}") == pytest.approx(10.0)  # (8 + 12) / 2
        assert read(f"layer_loop_ms.{cell}") == pytest.approx(5.0)
    assert read("chunk_attend_ms.itl") == pytest.approx(3.0)
    assert scopes.scope_ms(ctx, "decode", "/flash_decode/") == pytest.approx(6.0)
    # the runs are those of the accepted readers
    assert read("decode_step_ms.tok") == pytest.approx(21.0)
    # what no operation carries reads nothing
    assert scopes.scope_ms(ctx, "decode", "/moe/") is None


def test_the_recorded_trace_predates_the_scopes():
    """A program compiled without the scopes leaves their metrics out of
    the result line."""
    red = trace.reduce(str(RECORDED))
    prog = kernels.Program("\n".join(o.name for r in red.runs for o in r.ops),
                           {"head_dim": 64})
    ctx = harness.Context(spec={}, records=[], window=None, window_s=0.1,
                          setup_s=1.0, trace=red,
                          programs={"decode": prog, "prefill": prog})
    assert scopes.scope_ms(ctx, "decode", scopes.LAYER_LOOP) is None
    assert scopes.scope_ms(ctx, "prefill", "/chunk_attend/") is None


def test_existing_readers_read_as_before_on_the_recorded_trace():
    """Every reader the benchmark had before the program's own marks, and
    match_runs, read the recorded trace as they did then (the constants
    are their readings)."""
    red = trace.reduce(str(RECORDED))
    assert red.window == (0.049039481, 0.513287383)
    assert (red.busy_s, len(red.busy)) == (0.4562175569999999, 7251)
    assert [(r.kind, r.module.name, r.module.start, r.module.end, len(r.ops))
            for r in red.runs] == [
        ("prefill", "jit_fn(6636870049680814235)", 0.050237525000000005,
         0.23198907400000002, 4582),
        ("decode", "jit_fn(1505424238772498278)", 0.23506965200000002,
         0.509548495, 4103)]
    assert [e.name for e in red.host] == [
        "bench.release", "bench.step", "bench.prefill_call", "bench.decode_call"]
    assert trace.idle_by_span(red) == {
        "none": 0.0001020299999999405,
        "bench.release": 5.2249999999996743e-05,
        "bench.step": 0.007828864000000116,
        "bench.decode_call": 4.720100000002447e-05}
    spec = json.loads((ROOT / "bench" / "configs" / "granite-3-2b.json")
                      .read_text())
    # the trace names each operation by its instruction: a program table
    # built from those names finds the kernel by its signature
    prog = kernels.Program("\n".join(o.name for r in red.runs for o in r.ops),
                           spec)
    ctx = harness.Context(
        spec=spec, records=[], window=None, window_s=red.window_s,
        setup_s=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=red,
        calls={"decode": [[64 + 37 * i for i in range(32)]],
               "prefill": [(256, 200)]},
        programs={"decode": prog, "prefill": prog})
    assert readers.host_ms(ctx) == 7.876065000000154
    assert readers.program_ms(ctx, "decode") == 274.478843
    assert readers.program_ms(ctx, "prefill") == 181.751549
    assert readers.mfu(ctx, "decode") == 0.3122309690255564
    assert readers.mfu(ctx, "prefill") == 2.7835272048072635
    assert readers.idle_share(ctx) == 1.7297536435609162
    assert readers.flash_decode_roofline(ctx) == 8.870887293073578
