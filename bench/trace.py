"""Reduction of a ``jax.profiler`` trace to the numbers the readers need.

A trace is reduced to two lists on one clock (nanoseconds):

* device operations, ``Op(name, start, dur, chip)``: every event of an
  ``XLA Ops`` line of a ``/device:TPU:<n>`` plane;
* host spans, ``Span(name, start, dur)``: the ``TraceAnnotation`` events
  of the host plane, which is where the benchmark's own spans
  (``bench.window``, ``bench.step``, ...) and the program's
  (``serve.decode_step``, ...) land.

Everything below :func:`load` works on those plain lists, so a test can
check it on a hand-made trace.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
# host spans kept from a trace: the benchmark's own and the program's
SPAN_PREFIXES = ("bench.", "serve.", "train.")


def profile_options():
    """What the profiler records: device activity and the host's
    annotations, not every Python call (the Python tracer slows the host
    several fold and makes traces of hundreds of MB)."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: a TPU trace
    names an op by its whole HLO instruction."""
    if event_name.startswith("%") and " = " in event_name:
        return event_name[1:event_name.index(" = ")]
    return event_name


class Op(NamedTuple):
    name: str
    start: float
    dur: float
    chip: int


class Span(NamedTuple):
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


class Trace(NamedTuple):
    ops: List[Op]
    spans: List[Span]
    chips: int


def _chip_of(plane_name: str) -> Optional[int]:
    """``/device:TPU:3`` -> 3; None for any other plane (host, metadata,
    the TPU's non-core planes such as ``/device:TPU_NON_CORE:0``)."""
    head = "/device:TPU:"
    if not plane_name.startswith(head):
        return None
    tail = plane_name[len(head):]
    return int(tail) if tail.isdigit() else None


def load(logdir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``logdir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops: List[Op] = []
    spans: List[Span] = []
    chips = set()
    for plane in data.planes:
        chip = _chip_of(plane.name)
        if chip is not None:
            chips.add(chip)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(Op(op_name(e.name), e.start_ns,
                                  e.duration_ns, chip) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend(Span(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIXES))
    return Trace(ops, spans, len(chips))


def window_of(trace: Trace) -> Tuple[float, float]:
    """(start, end) of the benchmark's measured window on the trace clock."""
    win = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(win)}")
    return win[0].start, win[0].end


def union(intervals: Iterable[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals, clipped to [lo, hi]."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy(trace: Trace, window: Tuple[float, float]) -> float:
    """Seconds in which some operation ran on the device, averaged over the
    chips that appear in the trace."""
    lo, hi = window
    per_chip = defaultdict(list)
    for op in trace.ops:
        per_chip[op.chip].append((op.start, op.start + op.dur))
    if not per_chip:
        return 0.0
    total = sum(sum(b - a for a, b in union(iv, lo, hi))
                for iv in per_chip.values())
    return total / len(per_chip) / 1e9


def idle_gaps(trace: Trace, window: Tuple[float, float],
              chip: int = 0) -> List[Tuple[float, float]]:
    """(start, end) of every stretch of the window with no device op on
    ``chip``."""
    lo, hi = window
    covered = union(((o.start, o.start + o.dur) for o in trace.ops
                     if o.chip == chip), lo, hi)
    gaps, t = [], lo
    for a, b in covered:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_label(spans: Sequence[Span], t0: float, t1: float) -> str:
    """What the host was doing in (t0, t1): the innermost span open at its
    middle, the benchmark's window itself left aside."""
    mid = (t0 + t1) / 2
    open_ = [s for s in spans if s.start <= mid < s.end
             and s.name != WINDOW_SPAN]
    if not open_:
        return "no host span"
    return max(open_, key=lambda s: s.start).name


def op_seconds(trace: Trace, window: Tuple[float, float]
               ) -> Dict[str, float]:
    """Device seconds per operation name, over the ops that start in the
    window, summed over chips. An op's time is its own: a ``while`` or
    ``call`` event encloses the events of its body on the same line, and
    their time is left out of it."""
    lo, hi = window
    out: Dict[str, float] = defaultdict(float)
    per_chip = defaultdict(list)
    for op in trace.ops:
        if lo <= op.start < hi:
            per_chip[op.chip].append(op)
    for ops in per_chip.values():
        stack: List[list] = []           # [end, name, own time]
        for op in sorted(ops, key=lambda o: (o.start, -o.dur)):
            while stack and op.start >= stack[-1][0]:
                _, name, own = stack.pop()
                out[name] += own / 1e9
            if stack:
                stack[-1][2] -= op.dur
            stack.append([op.start + op.dur, op.name, op.dur])
        for _, name, own in stack:
            out[name] += own / 1e9
    return dict(out)


def breakdown(trace: Trace, window: Tuple[float, float], top: int = 10,
              label=None) -> Dict[str, list]:
    """The ``breakdown`` of a traced run's result line: the device
    operations that took most time, summed by ``label`` (an op's name to
    what it is, as :func:`bench.hlo.op_kinds` tells it), and the longest
    idle gaps on chip 0, each named by what the host was doing then."""
    label = label or (lambda n: n)
    per: Dict[str, float] = defaultdict(float)
    for name, s in op_seconds(trace, window).items():
        per[label(name)] += s
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, window), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[host_label(trace.spans, a, b), (b - a) / 1e9]
                          for a, b in gaps]}
