"""Peak rates of the chips this repo runs on, keyed by the device kind JAX
reports (``jax.devices()[0].device_kind``). Used by the roofline analysis,
the analytic autotuner and the executor's VMEM budget.

A chip that is not in the table is an error, not a default: its peaks
would silently be another chip's. On a host with no accelerator (CPU
tests, compiles for a described topology) the analytic model plans for
``TARGET``, the chip this repo is built for.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops_bf16: float  # FLOP/s per chip
    hbm_bandwidth: float  # bytes/s per chip
    ici_link_bandwidth: float  # bytes/s per link per direction
    ici_links: int  # links per chip (2D torus)
    hbm_bytes: int  # capacity per chip
    vmem_bytes: int
    # inter-pod (DCN-ish) effective per-chip bandwidth for the pod axis
    pod_link_bandwidth: float = 6.25e9
    # fixed per-message cost of one ICI transfer (hop latency + DMA
    # descriptor setup): what sub-chunking trades bandwidth against
    ici_msg_overhead: float = 1e-6


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of interchip interconnect (4 links of 50 GB/s);
# 128 MiB of VMEM per core. The ICI message overhead is a hand-set
# constant, not a fitted one.
TPU_V5E = HardwareSpec(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    ici_links=4,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
)

# device_kind -> peaks; the key is the string a v5e reports to JAX
PEAKS = {"TPU v5 lite": TPU_V5E}

TARGET = TPU_V5E


def spec_for(kind: str) -> HardwareSpec:
    """The peaks of the chip whose ``device_kind`` is ``kind``."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {kind!r} (known: "
            f"{sorted(PEAKS)}); add it to repro.hw.PEAKS with its source"
        ) from None


def local_spec() -> HardwareSpec:
    """The peaks of the accelerator this process runs on (an unknown
    kind raises); ``TARGET`` on a host without one."""
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return TARGET
    return spec_for(dev.device_kind)
