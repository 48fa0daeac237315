"""Low-overhead host-side span tracer with Chrome/Perfetto export.

A :class:`Tracer` records nested wall-clock spans around the hot host-side
loops (train step, prefill, decode step, admission, page allocation) and
exports them as Chrome trace-event JSON — a flat list of ``"ph": "X"``
complete events that ``chrome://tracing`` and https://ui.perfetto.dev load
directly (nesting is inferred from containment on one pid/tid track).

Design constraints, in order:

1. **Zero-cost disabled path.** ``Tracer(enabled=False).span(...)`` returns
   ONE module-level singleton no-op context manager — no object allocation,
   no clock read, no event append — so instrumentation can stay permanently
   compiled into the decode loop without taxing the benchmarked path. The
   module-level :data:`NULL_TRACER` is what un-instrumented call sites bind
   when no observability sink was passed in.
2. **Device alignment.** Host spans only see dispatch; with
   ``annotate_device=True`` each span also enters a
   ``jax.profiler.TraceAnnotation`` of the same name, so a device trace
   captured via :func:`device_trace` (``jax.profiler.start_trace``) lines
   its XLA activity up under the host span names in Perfetto.
3. **No timestamp surprises.** Spans are timed with ``perf_counter_ns``
   against a per-tracer origin, emitted in microseconds (the trace-event
   unit).

CLI: ``python -m repro.obs --label NAME [--out trace.json] -- cmd...``
runs ``cmd`` inside one span, prints ``[trace] NAME: <seconds>s``, and exits
with the command's status — scripts/test.sh uses it to report per-batch
wall time.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Dict, List, Optional


class _NullSpan:
    """The shared no-op span: enter/exit do nothing, allocate nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """One live span; records a complete ("X") event on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = args
        self._ann = None

    def __enter__(self):
        if self._tracer.annotate_device:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        tr = self._tracer
        ev = {"name": self._name, "ph": "X", "pid": tr.pid,
              "tid": threading.get_ident(),
              "ts": (self._t0 - tr.origin_ns) / 1e3,
              "dur": (t1 - self._t0) / 1e3}
        if self._args:
            ev["args"] = self._args
        tr.events.append(ev)
        return False


class Tracer:
    """Host-side span recorder; ``enabled=False`` is the zero-cost path."""

    def __init__(self, enabled: bool = True,
                 annotate_device: bool = False):
        self.enabled = enabled
        self.annotate_device = annotate_device
        self.pid = os.getpid()
        self.origin_ns = time.perf_counter_ns()
        self.events: List[Dict[str, Any]] = []

    def span(self, name: str, **args):
        """Context manager timing one span; kwargs become event args."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, args or None)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker event (``"ph": "i"``)."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "pid": self.pid,
              "tid": threading.get_ident(),
              "ts": (time.perf_counter_ns() - self.origin_ns) / 1e3}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def clear(self) -> None:
        self.events = []

    def to_chrome(self) -> List[Dict[str, Any]]:
        """The Chrome trace-event list (already loadable as-is)."""
        return list(self.events)

    def write_chrome(self, path: str) -> None:
        """Write the trace as Chrome/Perfetto-loadable JSON."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


#: The disabled tracer un-instrumented call sites bind to. Spans on it are
#: the singleton no-op; never enable it in place — make your own Tracer.
NULL_TRACER = Tracer(enabled=False)


class device_trace:
    """Context manager around ``jax.profiler.start_trace/stop_trace``:
    captures an XLA device trace under ``logdir`` alongside the host spans.
    Off the TPU a profiler that cannot start (already active, unsupported
    backend) degrades to a no-op with a warning; on the TPU the failure is
    raised, since a chip run whose trace was asked for and silently not
    taken would report what it never measured.
    """

    def __init__(self, logdir: str):
        self.logdir = logdir
        self._active = False

    def __enter__(self):
        import jax
        try:
            jax.profiler.start_trace(self.logdir)
            self._active = True
        except Exception as e:
            if jax.default_backend() == "tpu":
                raise
            import warnings
            warnings.warn(f"device trace unavailable: {e}")
        return self

    def __exit__(self, *exc):
        if self._active:
            import jax
            jax.profiler.stop_trace()
        return False


def _main() -> int:
    import argparse
    import subprocess
    import sys
    ap = argparse.ArgumentParser(
        description="run a command inside one tracer span and print its "
                    "wall time")
    ap.add_argument("--label", default="cmd")
    ap.add_argument("--out", default="",
                    help="write a Chrome trace JSON for the span")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- command to run")
    args = ap.parse_args()
    cmd = args.cmd[1:] if args.cmd[:1] == ["--"] else args.cmd
    if not cmd:
        ap.error("no command given (use: ... --label NAME -- cmd args)")
    tracer = Tracer(enabled=True)
    with tracer.span(args.label, cmd=" ".join(cmd)):
        rc = subprocess.call(cmd)
    dur_s = tracer.events[-1]["dur"] / 1e6
    print(f"[trace] {args.label}: {dur_s:.1f}s (exit {rc})", flush=True)
    if args.out:
        tracer.write_chrome(args.out)
    return rc


if __name__ == "__main__":                   # pragma: no cover - CLI
    raise SystemExit(_main())
