"""Roofline analysis from the compiled dry-run artifact (§Roofline).

Three terms per (arch x shape x mesh):

  compute    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory     = HLO_bytes / (chips * HBM_bw)
  collective = wire_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``. Collective
wire bytes are NOT in cost_analysis: we parse ``compiled.as_text()`` and
sum, per collective op, the bytes that actually cross a link per device:

  all-gather        out_bytes * (W-1)/W       (ring receive)
  reduce-scatter    in_bytes  * (W-1)/W
  all-reduce        2 * bytes * (W-1)/W       (RS + AG halves)
  collective-permute out_bytes                 (one hop)
  all-to-all        out_bytes * (W-1)/W

Ops inside a scanned layer loop (detected via the ``while`` marker in the
op metadata) execute n_super times; the parser multiplies them by the
supplied trip count. cost_analysis' loop handling is validated in tests
against an analytic 6ND model (the MODEL_FLOPS/HLO_FLOPs ratio column).
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass
from typing import Dict, Optional

from .. import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s*"
    r"(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{")
_PARAM_CONVERT_RE = re.compile(
    r"%wrapped_convert[.\d]* = (f32\[[0-9,]+\])[^\n]*fusion\(%param[.\d]*\)"
)


def cpu_bf16_artifact_bytes(hlo_text: str) -> float:
    """Sum f32 convert-of-parameter fusion buffers (see RooflineReport)."""
    total = 0.0
    for m in _PARAM_CONVERT_RE.finditer(hlo_text):
        total += _type_bytes(m.group(1))
    return total


def _type_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    wire_bytes: float  # per device, trip-multiplied
    op_counts: Dict[str, int]
    op_bytes: Dict[str, float]


def parse_collectives(hlo_text: str, *, loop_trips: int = 1) -> CollectiveStats:
    """Sum per-device wire bytes of every collective in compiled HLO text.

    ``-done`` halves of async pairs carry no shape work and are skipped by
    the regex (only the defining ``...-start(`` / sync form matches).
    """
    wire = 0.0
    counts: Dict[str, int] = {}
    bytes_by: Dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        out_bytes = _type_bytes(type_str)
        gm = _GROUPS_RE.search(line)
        w = len(gm.group(1).split(",")) if gm else 2
        frac = (w - 1) / w if w > 1 else 1.0
        if op == "all-gather":
            b = out_bytes * frac
        elif op == "reduce-scatter":
            b = out_bytes * w * frac  # operand bytes ~ out * W
        elif op == "all-reduce":
            b = 2.0 * out_bytes * frac
        elif op == "all-to-all":
            b = out_bytes * frac
        else:  # collective-permute: one hop, full buffer
            b = float(out_bytes)
        trips = loop_trips if "while" in line else 1
        wire += b * trips
        counts[op] = counts.get(op, 0) + trips
        bytes_by[op] = bytes_by.get(op, 0.0) + b * trips
    return CollectiveStats(wire, counts, bytes_by)


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # raw
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    coll_bytes_per_device: float
    model_flops_total: float
    # terms (seconds)
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    useful_flops_ratio: float
    # memory footprint
    device_bytes: float
    fits_hbm: bool
    # CPU-backend artifact: XLA:CPU has no native bf16 dot, so it inserts
    # f32 converts of the dot operands and HOISTS the loop-invariant weight
    # converts out of the layer scan — whole-parameter-stack f32 copies
    # that do NOT exist on TPU (bf16 feeds the MXU directly). We count
    # those hoisted param-convert buffers and report an adjusted figure.
    cpu_bf16_artifact_bytes: float
    device_bytes_tpu_adjusted: float
    fits_hbm_adjusted: bool
    collective_detail: Dict[str, float]

    def to_json(self) -> str:
        return json.dumps(asdict(self), indent=1)


def normalize_cost(cost) -> dict:
    """``compiled.cost_analysis()`` returns a dict on current jax and a
    one-element list of dicts on older builds; accept both."""
    if isinstance(cost, (list, tuple)):
        return dict(cost[0]) if cost else {}
    return cost


def analyze(
    *,
    arch: str,
    shape_name: str,
    mesh_desc: str,
    chips: int,
    cost: dict,
    memory_stats,
    hlo_text: str,
    loop_trips: int,
    model_flops_total: float,
    spec: Optional[hw.HardwareSpec] = None,
    links_used: int = 1,
    backward: bool = True,
) -> RooflineReport:
    """Build the three-term roofline report for one dry-run cell.

    cost_analysis on this JAX/XLA build does NOT multiply while-loop bodies
    by their trip count (validated in tests/test_roofline.py), so we scale
    flops/bytes by ``loop_trips`` for the scanned layer stack. The
    unscanned head/tail is a small correction, folded into the ratio
    column rather than double-counted.
    """
    spec = spec or hw.local_spec()
    cost = normalize_cost(cost)
    flops_dev = float(cost.get("flops", 0.0)) * loop_trips
    bytes_dev = float(cost.get("bytes accessed", 0.0)) * loop_trips
    coll = parse_collectives(hlo_text, loop_trips=loop_trips)

    t_comp = flops_dev / spec.peak_flops_bf16
    t_mem = bytes_dev / spec.hbm_bandwidth
    t_coll = coll.wire_bytes / (spec.ici_link_bandwidth * links_used)
    dominant = max(
        (("compute", t_comp), ("memory", t_mem), ("collective", t_coll)),
        key=lambda kv: kv[1],
    )[0]
    flops_total = flops_dev * chips
    ratio = model_flops_total / flops_total if flops_total else 0.0
    dev_bytes = float(
        memory_stats.output_size_in_bytes
        + memory_stats.temp_size_in_bytes
        + memory_stats.argument_size_in_bytes
        - memory_stats.alias_size_in_bytes
    )
    # fwd (+ bwd when training) keep hoisted f32 weight-convert copies on CPU
    artifact = (2.0 if backward else 1.0) * cpu_bf16_artifact_bytes(hlo_text)
    adjusted = max(dev_bytes - artifact, 0.0)
    return RooflineReport(
        arch=arch,
        shape=shape_name,
        mesh=mesh_desc,
        chips=chips,
        hlo_flops_per_device=flops_dev,
        hlo_bytes_per_device=bytes_dev,
        coll_bytes_per_device=coll.wire_bytes,
        model_flops_total=model_flops_total,
        t_compute=t_comp,
        t_memory=t_mem,
        t_collective=t_coll,
        dominant=dominant,
        useful_flops_ratio=ratio,
        device_bytes=dev_bytes,
        fits_hbm=dev_bytes <= spec.hbm_bytes,
        cpu_bf16_artifact_bytes=artifact,
        device_bytes_tpu_adjusted=adjusted,
        fits_hbm_adjusted=adjusted <= spec.hbm_bytes,
        collective_detail=coll.op_bytes,
    )
