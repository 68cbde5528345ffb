"""The reduction from a profiler trace to the per-layer metrics, on a
hand-made trace whose answers are known."""
import json

import pytest
from jax.profiler import ProfileData

from bench import harness, kernels, trace
from bench.tests.tiny import ROOT

MS = 10**9  # picoseconds in a millisecond

# Window 0-100 ms. Host: two engine steps; the first launches a decode,
# the second a prefill and then a decode. Device: the three runs, each
# made of operations; the decode's kernel is closed_call.3.
HOST = [("bench.window", 0, 100), ("bench.step", 10, 40),
        ("bench.decode_call", 12, 14), ("bench.step", 50, 90),
        ("bench.prefill_call", 52, 54), ("bench.decode_call", 60, 62)]
MODULES = [("jit_fn(11)", 15, 35), ("jit_fn(22)", 55, 58),
           ("jit_fn(11)", 63, 85)]
# as a TPU names them: by instruction; the loop spans its body
WHILE = "%while.1 = (s32[], bf16[4,64]{1,0}) while((s32[], bf16[4,64]{1,0}) %t)"
K = "%closed_call.3 = (f32[4,2,2,16]{3,2,1,0}, f32[4,2,2,128]{3,2,1,0}) custom-call(%a)"
F9 = "%fusion.9 = bf16[4,64]{1,0} fusion(%c), kind=kLoop"
OPS = [(WHILE, 15, 35), (K, 15, 25), (F9, 25, 35),
       ("%fusion.2 = bf16[1,256,64]{2,1,0} fusion(%d)", 55, 58),
       (K, 63, 70), (F9, 70, 85)]
DECODE_HLO = """
HloModule jit_fn
  %closed_call.3 = (f32[4,2,2,16]{3,2,1,0}, f32[4,2,2,128]{3,2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/while/body/pallas_call"}
  %fusion.9 = bf16[4,64]{1,0} fusion(%c), kind=kLoop, metadata={op_name="jit(fn)/while/body/gather"}
"""


def _line(lid, name, events, names):
    out = [f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0']
    for ev, a, b in events:
        out.append(f"events {{ metadata_id: {names[ev]} offset_ps: {a * MS} "
                   f"duration_ps: {(b - a) * MS} }}")
    return "\n".join(out) + " }"


def _plane(pid, name, lines):
    names = {}
    for _, events in lines:
        for ev, _, _ in events:
            names.setdefault(ev, len(names) + 1)
    body = [f'planes {{ id: {pid} name: "{name}"']
    body += [_line(i + 1, ln, evs, names) for i, (ln, evs) in enumerate(lines)]
    body += [f'event_metadata {{ key: {v} value {{ id: {v} name: {json.dumps(k)} }} }}'
             for k, v in names.items()]
    return "\n".join(body) + " }"


@pytest.fixture
def reduced(tmp_path):
    text = "\n".join([
        _plane(1, "/device:TPU:0", [("XLA Modules", MODULES), ("XLA Ops", OPS)]),
        _plane(2, "/host:CPU", [("python", HOST)]),
    ])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce(str(path))


def test_busy_runs_and_idle_by_span(reduced):
    assert reduced.window == (0.0, pytest.approx(0.1))
    assert reduced.busy_s == pytest.approx(0.045)
    assert [r.kind for r in reduced.runs] == ["decode", "prefill", "decode"]
    assert [round(r.module.dur * 1e3, 6) for r in reduced.runs] == [20, 3, 22]
    assert [len(r.ops) for r in reduced.runs] == [2, 1, 2]
    idle = {k: round(v * 1e3, 6) for k, v in trace.idle_by_span(reduced).items()}
    assert idle == {"none": 30, "bench.step": 19, "bench.decode_call": 4,
                    "bench.prefill_call": 2}


def test_readers_on_the_reduced_trace(reduced):
    spec = {"num_hidden_layers": 1, "hidden_size": 64, "head_dim": 16,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "intermediate_size": 128, "vocab_size": 256}
    prog = kernels.Program(DECODE_HLO, spec)
    assert prog.kernels == {"flash_decode": {"closed_call.3"}}
    assert prog.label(reduced.runs[0].ops[1]) == "while/body/gather"
    peaks = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = harness.Context(spec=spec, records=[], window=None,
                          window_s=0.1, setup_s=1.0, peaks=peaks,
                          trace=reduced, programs={"decode": prog},
                          calls={"decode": [[9, 19], [30]], "prefill": [(0, 7)]})

    def read(name):
        return harness.load_reader(ROOT / "bench" / "metrics" / f"{name}.py")(ctx)

    assert read("decode_step_ms.itl") == pytest.approx(21.0)
    assert read("prefill_chunk_ms.itl") == pytest.approx(3.0)
    assert read("device.idle_share.itl") == pytest.approx(55.0)
    assert read("engine.host_ms.itl") == pytest.approx(12.5)
    # the kernel: 17 ms over both runs; K/V of 10 + 20 + 31 positions
    kv = 2 * 2 * 16 * 2 * (10 + 20 + 31)
    qo = 3 * 4 * 16 * 6
    flops = 4 * 4 * 16 * (10 + 20 + 31)
    least = max(flops / 1e12, (kv + qo) / 1e9)
    assert read("flash_decode_roofline.itl") == pytest.approx(100 * least / 0.017)
    # a program without the kernel leaves its roofline silent, not zero
    ctx.programs = {"decode": kernels.Program("HloModule m", spec)}
    assert read("flash_decode_roofline.itl") is None


def test_a_mismatch_of_calls_and_runs_reads_nothing(reduced):
    ctx = harness.Context(spec={}, records=[], window=None,
                          window_s=0.1, setup_s=1.0, trace=reduced,
                          calls={"decode": [[1]], "prefill": []})
    from bench import readers

    assert readers.runs(ctx, "decode") == []


RECORDED = ROOT / "bench" / "tests" / "data" / "granite_decode_heavy.xplane.pb"


def test_a_trace_recorded_on_a_tpu_v5e():
    """0.46 s of granite-3-2b under decode-heavy traffic (one step: a
    prefill chunk, then a decode of 32 slots), traced on one TPU v5e."""
    red = trace.reduce(str(RECORDED))
    assert red.window_s == pytest.approx(0.464247902)
    assert red.busy_s == pytest.approx(0.456217557)
    assert [(r.kind, round(r.module.dur, 9)) for r in red.runs] == [
        ("prefill", 0.181751549), ("decode", 0.274478843)]
    # the decode program runs the flash-decode kernel once per layer
    spec = {"head_dim": 64}
    found = [kernels.classify(o.name.split(" = ", 1)[1], spec)
             for r in red.runs for o in r.ops if "tpu_custom_call" in o.name]
    assert found == ["flash_decode"] * 40
    idle = trace.idle_by_span(red)
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s)
    assert max(idle, key=idle.get) == "bench.step"
