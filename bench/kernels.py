"""Which device operations are which kernel, read from the compiled
programs' HLO.

A Pallas kernel is a ``tpu_custom_call`` instruction. The program gives
its kernels no names of their own, so a kernel is known by its
signature: ``flash_decode`` returns a pair of f32 arrays, the output
(B, Hkv, G, head_dim) and the log-sum-exp broadcast over 128 lanes
(B, Hkv, G, 128). Other kernels are ``other_kernel``.

A trace event of a device operation is named by its instruction
(``%name = ...``), so a program's table maps it back. An instruction's
``op_name`` metadata (its place in the traced function) names the
operations of the breakdown.
"""
from __future__ import annotations

import re
from typing import Dict, Set

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")


def _result_shapes(rhs: str):
    """The result type of an instruction: [(dtype, dims), ...]."""
    head = rhs.split(" custom-call(")[0] if " custom-call(" in rhs else rhs
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(head)]


def classify(rhs: str, spec: dict) -> str:
    """The kernel a ``tpu_custom_call`` instruction is, or "" if none
    of the known ones."""
    shapes = _result_shapes(rhs)
    hd = spec["head_dim"]
    if (len(shapes) == 2 and all(dt == "f32" and len(d) == 4
                                 for dt, d in shapes)
            and shapes[0][1][-1] == hd and shapes[1][1][-1] == 128):
        return "flash_decode"
    return ""


class Program:
    """The instructions of one compiled program: their op_name, and
    which are kernels."""

    def __init__(self, hlo_text: str, spec: dict):
        self.op_name: Dict[str, str] = {}
        self.kernels: Dict[str, Set[str]] = {}
        for line in hlo_text.splitlines():
            m = _INSTR.match(line)
            if not m:
                continue
            name, rhs = m.groups()
            on = _OP_NAME.search(rhs)
            if on:
                self.op_name[name] = on.group(1)
            if "tpu_custom_call" in rhs:
                kind = classify(rhs, spec) or "other_kernel"
                self.kernels.setdefault(kind, set()).add(name)

    def is_kernel(self, kernel: str):
        names = self.kernels.get(kernel, set())
        return lambda op: op_name(op) in names

    def label(self, op) -> str:
        name = op_name(op)
        for kernel, names in self.kernels.items():
            if name in names:
                return kernel
        where = self.op_name.get(name, name)
        return re.sub(r"^jit\([^)]*\)/", "", where)


_EVENT = re.compile(r"^%?([\w.\-]+) = ")


def op_name(op) -> str:
    """The instruction a trace event of the device ran."""
    m = _EVENT.match(op.name)
    return m.group(1) if m else op.name
