"""Serve a small LM with batched requests through the paged engine.

  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python examples/serve_lm.py
"""
import argparse
import sys

sys.path.insert(0, "src")

from repro.launch import serve as serve_mod  # noqa: E402


def main():
    import jax

    ndev = jax.device_count()
    ns = argparse.Namespace(
        arch="granite-3-2b", reduced=True,
        dp=2 if ndev >= 4 else 1, tp=2 if ndev >= 4 else 1,
        batch=4, max_len=64, requests=8, new_tokens=8, temperature=0.7,
        dtype="float32", no_fsdp=False)
    eng = serve_mod.run(ns)
    m = eng.metrics()
    print(f"\nTTFT {m.ttft_mean_s * 1e3:.1f}ms mean / "
          f"{m.ttft_max_s * 1e3:.1f}ms max; "
          f"TPOT {m.tpot_mean_s * 1e3:.2f}ms; "
          f"queue depth {m.queue_depth_mean:.2f} mean "
          f"(max {m.queue_depth_max}); "
          f"slot occupancy {m.slot_occupancy_mean:.0%}")


if __name__ == "__main__":
    main()
