"""Tiny configurations for running the harness on the CPU.

The program's own small presets (``repro.configs.reduced``) stand in for
the cells' configurations: same families, widths a CPU runs in seconds.
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY = {
    "dense": {"name": "tiny-dense", "source": "repro.configs.reduced",
              "program_arch": "granite-3-2b", "num_hidden_layers": 2,
              "hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "vocab_size": 256, "rope_theta": 10000.0,
              "rms_norm_eps": 1e-05, "hidden_act": "silu",
              "tie_word_embeddings": True},
    "moe": {"name": "tiny-moe", "source": "repro.configs.reduced",
            "program_arch": "granite-moe-3b-a800m", "num_hidden_layers": 2,
            "hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 16, "num_local_experts": 4, "num_experts_per_tok": 2,
            "capacity_factor": 2.0, "vocab_size": 256, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-05, "hidden_act": "silu",
            "tie_word_embeddings": True},
}
SERVE = {"batch": 4, "max_len": 64, "page_size": 8, "chunk": 16,
         "token_budget": 20, "num_pages": 24, "queue_cap": 64}
MIXES = {
    "open": {"loop": "open", "rate_rps": 40.0, "warm_s": 0.2,
             "prime": {"requests": 2, "prompt_max": 8},
             "prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
             "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 20}},
    "closed": {"loop": "closed", "clients": 4, "warm_s": 0.1,
               "prime": {"prompt_max": 8},
               "window_requests": 5000,
               "prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 40},
               "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 20}},
}


def tiny_spec(family: str) -> dict:
    return dict(TINY[family], serve=dict(SERVE))


# the tiny cells' limit on the widest logit gap: served to the end, their
# sound runs read at most 0.0036 and the float8 control at least 0.167
# (seeds 1-3, both families, CPU); timed runs read up to 0.019
LIMIT = 0.08
