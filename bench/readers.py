"""What the per-layer readers in ``bench/metrics/`` share. Each reader is a
file of its own that calls one of these; a reader that finds nothing to
read returns None and the metric is left out of the line."""
from __future__ import annotations

from typing import Callable, Optional

from bench import hlo, trace as T
from bench.counts import least_seconds


def idle_share(ctx) -> float:
    """Per cent of the traced window in which no operation ran on the
    device (union of op intervals, averaged over chips)."""
    lo, hi = ctx.window
    return 100.0 * (1.0 - T.busy(ctx.trace, ctx.window) / ((hi - lo) / 1e9))


def mfu(ctx) -> Optional[float]:
    """Per cent of the chips' bf16 peak that the window's model flops fill:
    flops per item times items, over the window's time."""
    f = ctx.outcome.facts
    if not f.get("items"):
        return None
    flops = f["flops_per_item"] * f["items"]
    return 100.0 * flops / (ctx.cell.window_s * ctx.peaks["bf16_flops_per_s"]
                            * len(ctx.cell.devices))


def kernel_roofline(ctx, program: str, pick: Callable, expected_calls: int,
                    flops: float, bytes_: float) -> Optional[float]:
    """Per cent of a kernel's device time that the chip's roofline says it
    needs: the least time for ``flops`` and ``bytes_`` (the window's calls
    of that kernel, counted from shapes), over the summed device time of
    its events in the trace. ``pick(kernel)`` tells its custom calls (by
    their shapes) from the program's other kernels; the number of events
    has to be ``expected_calls``."""
    kernels = {name for name, k in hlo.custom_calls(
        ctx.outcome.facts["hlo"][program]).items() if pick(k)}
    if not kernels:
        return None
    lo, hi = ctx.window
    events = [op for op in ctx.trace.ops
              if op.name in kernels and lo <= op.start < hi]
    if len(events) != expected_calls:
        raise ValueError(f"{len(events)} kernel events in the window where "
                         f"the model implies {expected_calls}")
    busy = sum(op.dur for op in events) / 1e9
    return 100.0 * least_seconds(flops, bytes_, ctx.peaks) / busy


def program_spans(ctx, name: str):
    """(start, end) in host seconds of the program's own spans called
    ``name`` that start in the window."""
    lo, hi = ctx.outcome.facts["window"]
    return [(a, b) for n, a, b in ctx.outcome.facts.get("spans", ())
            if n == name and lo <= a < hi]
