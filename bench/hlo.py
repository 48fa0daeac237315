"""What the compiled step programs say about their Pallas kernels.

The program gives its ``pallas_call``s no names, so a kernel's events in a
device trace carry only the HLO instruction name (``closed_call.17``,
``jvp__.2``). This module reads the optimized HLO text of the compiled
programs the window drives and returns, for each ``tpu_custom_call``
instruction, its result and operand shapes; a reader then tells the
kernels apart by shape.
"""
from __future__ import annotations

import re
from typing import Dict, List, NamedTuple, Tuple

_ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|s32|u32|s64|u64|bf16|f16|f32|f64)"
                    r"\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s+custom-call\(")


class Shape(NamedTuple):
    dtype: str
    dims: Tuple[int, ...]


class Kernel(NamedTuple):
    name: str
    results: List[Shape]
    operands: List[Shape]


def _shapes(text: str) -> List[Shape]:
    return [Shape(m.group(1), tuple(int(d) for d in m.group(2).split(",")
                                    if d))
            for m in _ARRAY.finditer(text)]


def custom_calls(hlo_text: str) -> Dict[str, Kernel]:
    """Every ``tpu_custom_call`` of the module, by instruction name."""
    out: Dict[str, Kernel] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = _INSTR.match(line)
        if m is None:
            continue
        rest = line[m.end():]
        constraints = re.search(r"operand_layout_constraints=\{([^}]*)\}",
                                rest)
        operands = _shapes(constraints.group(1)) if constraints else []
        out[m.group(1)] = Kernel(m.group(1), _shapes(m.group(2)), operands)
    return out


_OP = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_HEAVY = ("convolution", "dot", "custom-call", "all-reduce", "all-gather",
          "reduce-scatter", "scatter", "gather", "sort")


def op_kinds(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> what it does, for naming trace events: the
    heaviest kind of op inside a fusion (``fusion:convolution``), ``pallas``
    for a Pallas kernel, else the opcode."""
    comps: Dict[str, set] = {}
    ops: Dict[str, tuple] = {}
    current = None
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and "=" not in line.split("{")[0]:
            current = m.group(1)
            comps[current] = set()
            continue
        m = _OP.match(line)
        if m is None or current is None:
            continue
        name, opcode = m.group(1), m.group(2)
        if 'custom_call_target="tpu_custom_call"' in line:
            opcode = "pallas"
        comps[current].add(opcode)
        calls = _CALLS.search(line)
        ops[name] = (opcode, calls.group(1) if calls else None)
    out = {}
    for name, (opcode, called) in ops.items():
        if opcode == "fusion" and called in comps:
            inner = comps[called]
            heavy = [k for k in _HEAVY if k in inner]
            out[name] = f"fusion:{heavy[0]}" if heavy else "fusion"
        else:
            out[name] = opcode
    return out
