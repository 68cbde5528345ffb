"""The readers of the routed-expert scopes (bench/moe_work.py) on a
hand-made trace whose answers are known, and on the recorded trace of a
program without the scopes, where they read nothing."""
import pytest
from jax.profiler import ProfileData

from bench import harness, kernels, moe_work, peaks, trace
from bench.tests.test_scopes import MODULES, SERVE_HOST
from bench.tests.test_trace import RECORDED, _plane
from bench.tests.tiny import ROOT

SPEC = {"num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 32,
        "num_local_experts": 4, "num_experts_per_tok": 2, "head_dim": 16}
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
DEC = "jit(paged_decode_step)/layers/while/body/closed_call/moe"
PRE = "jit(prefill_chunk_step)/layers/while/body/closed_call/checkpoint/moe"
GMM = "%{} = bf16[4,8,64]{{2,1,0}} custom-call(%x, %w)"
# decode runs 15-35 and 63-85 ms, the prefill run 55-58 ms (test_scopes)
OPS = [(GMM.format("gmm.1"), 15, 25), ("%fusion.5 = s32[2,2]{1,0} fusion(%l)", 25, 27),
       (GMM.format("gmm.2"), 55, 57), ("%scatter.3 = bf16[4,8,64]{2,1,0} scatter(%z)", 57, 58),
       (GMM.format("gmm.1"), 63, 73), ("%fusion.6 = bf16[2,64]{1,0} fusion(%g)", 73, 76)]
DECODE_HLO = f"""
HloModule jit_paged_decode_step
  %gmm.1 = bf16[4,8,64]{{2,1,0}} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{DEC}/experts/pallas_call"}}
  %fusion.5 = s32[2,2]{{1,0}} fusion(%l), kind=kLoop, metadata={{op_name="{DEC}/router/top_k"}}
  %fusion.6 = bf16[2,64]{{1,0}} fusion(%g), kind=kLoop, metadata={{op_name="{DEC}/combine/gather"}}
"""
PREFILL_HLO = f"""
HloModule jit_prefill_chunk_step
  %gmm.2 = bf16[4,8,64]{{2,1,0}} custom-call(%x, %w), custom_call_target="tpu_custom_call", metadata={{op_name="{PRE}/checkpoint/experts/pallas_call"}}
  %scatter.3 = bf16[4,8,64]{{2,1,0}} scatter(%z), metadata={{op_name="{PRE}/checkpoint/dispatch/scatter-add"}}
"""


@pytest.fixture
def ctx(tmp_path):
    text = "\n".join([
        _plane(1, "/device:TPU:0", [("XLA Modules", MODULES), ("XLA Ops", OPS)]),
        _plane(2, "/host:CPU", [("python", SERVE_HOST)]),
    ])
    path = tmp_path / "moe.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return harness.Context(
        spec=SPEC, records=[], window=None, window_s=0.1, setup_s=1.0,
        peaks=PEAKS, trace=trace.reduce(str(path)),
        calls={"decode": [[9, 19], [30]], "prefill": [(0, 7)]},
        programs={"decode": kernels.Program(DECODE_HLO, SPEC),
                  "prefill": kernels.Program(PREFILL_HLO, SPEC)})


def _read(ctx, name):
    return harness.load_reader(ROOT / "bench" / "metrics" / f"{name}.py")(ctx)


def test_expert_and_routing_time_per_prefill_run(ctx):
    assert _read(ctx, "expert_gemm_ms.pfh") == pytest.approx(2.0)
    assert _read(ctx, "moe_route_ms.pfh") == pytest.approx(1.0)


def test_expert_gemm_work_counts_routed_rows_and_reachable_experts():
    # 2 tokens x top-2: 4 rows through 3 matrices of 64 x 32, 2 layers;
    # the 4 rows reach at most 4 experts, each 3 x 64 x 32 bf16 weights
    flops, nbytes = moe_work.expert_gemm_work(SPEC, 2)
    layers, bf16 = 2, 2
    assert flops == layers * 2 * 4 * 3 * 64 * 32
    assert nbytes == layers * bf16 * (4 * 3 * 64 * 32 + 4 * 2 * 64)
    # one token reaches 2 experts, not the 4 the capacity buffers hold
    assert moe_work.expert_gemm_work(SPEC, 1)[1] == \
        layers * bf16 * (2 * 3 * 64 * 32 + 2 * 2 * 64)


def test_expert_gemm_roofline_over_both_programs(ctx):
    # decode calls route 2 and 1 tokens, the prefill call 7; all memory
    # bound at these peaks; 10 + 2 + 10 ms under moe/experts
    least = sum(max(f / 1e12, b / 1e9) for f, b in (
        moe_work.expert_gemm_work(SPEC, t) for t in (2, 1, 7)))
    assert _read(ctx, "expert_gemm_roofline.pfh") == \
        pytest.approx(100 * least / 0.022)


def test_a_program_without_the_scopes_reads_nothing():
    red = trace.reduce(str(RECORDED))
    prog = kernels.Program("\n".join(o.name for r in red.runs for o in r.ops),
                           {"head_dim": 64})
    ctx = harness.Context(
        spec=SPEC, records=[], window=None, window_s=red.window_s,
        setup_s=1.0, peaks=peaks.peaks_for("TPU v5 lite"), trace=red,
        calls={"decode": [[64] * 32], "prefill": [(256, 200)]},
        programs={"decode": prog, "prefill": prog})
    for name in ("expert_gemm_ms.pfh", "moe_route_ms.pfh",
                 "expert_gemm_roofline.pfh"):
        assert _read(ctx, name) is None
