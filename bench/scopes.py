"""Device time by sublayer, from the program's named scopes.

The program runs each sublayer under a ``jax.named_scope`` (``gbn``,
``conv``, ``attention``, ...) and each Pallas kernel under a scope and a
``pallas_call`` name of its own (``flash_attention``,
``gbn_fwd_stats``, ...). The names reach the compiled program as each
instruction's ``metadata={op_name="..."}``, a path such as
``jit(train_step)/transpose(jvp(stage1))/block3/conv/conv_general_dilated``.
A device trace names its events by instruction only, so this module reads
the optimized HLO text the drivers keep in ``facts["hlo"]`` and:

* maps every instruction to the scopes on its path (:func:`op_scopes`),
  JAX's wrappers (``jit(..)``, ``jvp(..)``, ``transpose(..)``, remat's
  ``checkpoint``, ``while``/``body``, ...) and the primitive at the path's
  end stripped. A fusion takes the path of its heaviest inner op (a
  convolution, dot or kernel) where that names a sublayer, else its own;
  an instruction the compiler made without metadata takes the path of its
  first operand that has one;
* sums each op's own time in the window by its innermost sublayer
  (:func:`seconds_by_sublayer`), through :func:`bench.trace.op_seconds`,
  so that a ``while`` does not count its body twice. Ops whose path names
  no sublayer go to ``unscoped``.

Forward, remat's recomputation and backward of a sublayer all count
towards it (JAX keeps the scope inside ``jvp(..)``/``transpose(..)``).
Instruction names are matched within the programs in ``facts["hlo"]``;
the few device ops of other, tiny programs run in the window (the feed's
key folding) are matched by name too, at microseconds a step.
"""
from __future__ import annotations

import functools
import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench import trace as T

# innermost first wins; a path names one of them
SUBLAYERS = ("gbn", "conv", "act", "head", "attention", "ssm", "mlp", "moe",
             "norm", "embed", "ce", "optimizer")
UNSCOPED = "unscoped"

# transforms whose argument is the scope they were applied inside
_TRANSFORMS = ("jvp", "transpose", "vmap", "pmap", "remat", "checkpoint")
# components that are JAX's or XLA's structure, not the program's scopes
_STRUCTURE = re.compile(r"^(while|body|cond|closed_call|checkpoint|remat|"
                        r"rematted_computation|pallas_call|scan|"
                        r"branch_\d+_fun|custom_jvp_call|custom_vjp_call)$")
_HEAVY = ("custom-call", "convolution", "dot")

_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)"
                    r"\((.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def _split(path: str) -> List[str]:
    """``a/f(b/c)/d`` -> ``["a", "f(b/c)", "d"]``: slashes outside
    parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(path[start:i])
            start = i + 1
    out.append(path[start:])
    return out


def _components(parts: List[str]) -> List[str]:
    out: List[str] = []
    for part in parts:
        m = re.fullmatch(r"([\w.]+)\((.*)\)", part)
        if m and m.group(1) in _TRANSFORMS:
            out.extend(_components(_split(m.group(2))))
        elif m or not part or _STRUCTURE.match(part):
            continue                     # jit(f), pjit(f): a function name
        else:
            out.append(part)
    return out


def scopes(op_name: str) -> List[str]:
    """The program's scopes on an ``op_name`` path, outermost first, with
    JAX's wrappers and the primitive at its end stripped. Several paths
    joined by ``;`` (instructions the compiler merged) give the first that
    names a sublayer."""
    paths = [_components(_split(p)[:-1]) for p in op_name.split(";")]
    for comps in paths:
        if any(c in SUBLAYERS for c in comps):
            return comps
    return paths[0]


def sublayer(comps: List[str]) -> str:
    """The innermost sublayer on a path of scopes, else ``unscoped``."""
    for c in reversed(comps):
        if c in SUBLAYERS:
            return c
    return UNSCOPED


class _Instr(NamedTuple):
    name: str
    opcode: str
    op_name: Optional[str]
    calls: Optional[str]          # the fused computation of a fusion
    operands: List[str]


def _parse(hlo_text: str) -> Dict[str, List[_Instr]]:
    comps: Dict[str, List[_Instr]] = {}
    current: Optional[List[_Instr]] = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _HEADER.match(line)
            if m and line.rstrip().endswith("{"):
                current = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m is None or current is None:
            continue
        meta = _OP_NAME.search(line)
        calls = _CALLS.search(line)
        args = m.group(3).split(")", 1)[0]
        current.append(_Instr(m.group(1), m.group(2),
                              meta.group(1) if meta else None,
                              calls.group(1) if calls else None,
                              _OPERAND.findall(args)))
    return comps


@functools.lru_cache(maxsize=4)
def op_scopes(hlo_text: str) -> Dict[str, List[str]]:
    """Instruction name -> its scopes (outermost first), for every
    instruction of the module (cached: the table returned is shared, not
    to be changed)."""
    comps = _parse(hlo_text)
    out: Dict[str, List[str]] = {}
    for instrs in comps.values():
        local: Dict[str, List[str]] = {}
        for ins in instrs:
            comps_of = scopes(ins.op_name) if ins.op_name else None
            if ins.opcode == "fusion" and ins.calls in comps:
                inner = [i for i in comps[ins.calls] if i.op_name]
                heavy = [scopes(i.op_name) for k in _HEAVY for i in inner
                         if i.opcode == k]
                named = [c for c in heavy if sublayer(c) != UNSCOPED]
                if named:
                    comps_of = named[0]
                elif comps_of is None and inner:
                    comps_of = scopes(inner[-1].op_name)
            if comps_of is None:
                comps_of = next((local[o] for o in ins.operands
                                 if o in local), [])
            local[ins.name] = comps_of
        out.update(local)
    return out


def seconds_by_sublayer(trace: T.Trace, window: Tuple[float, float],
                        hlo_texts) -> Dict[str, float]:
    """Device seconds in the window by sublayer (and ``unscoped``), summed
    over chips; every op's own time counts once."""
    paths: Dict[str, List[str]] = {}
    for text in hlo_texts:
        paths.update(op_scopes(text))
    out: Dict[str, float] = defaultdict(float)
    for name, s in T.op_seconds(trace, window).items():
        out[sublayer(paths.get(name, []))] += s
    return dict(out)


def ms_per_step(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per step of the ops under ``scope`` in a traced
    training window, per chip; None where no instruction of the step
    carries the scope (a program without the names)."""
    texts = list(ctx.outcome.facts.get("hlo", {}).values())
    if not any(sublayer(p) == scope for t in texts
               for p in op_scopes(t).values()):
        return None
    secs = seconds_by_sublayer(ctx.trace, ctx.window, texts)
    chips = max(ctx.trace.chips, 1)
    return 1e3 * secs.get(scope, 0.0) / ctx.outcome.facts["steps"] / chips
