"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip that is not here is an error, not a
default."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 393 TOP/s
# in int8, 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None
