"""The routed experts' grouped GEMMs' share of their roofline over the
decode and prefill runs, in % (moe_work.expert_gemm_roofline); silent
where the programs carry no ``moe/experts`` scope."""
from bench import moe_work


def read(ctx):
    return moe_work.expert_gemm_roofline(ctx)
