"""Tiny runs of a cell for the tests: a sound one, one with a fault planted
underneath the timed path, and the control's readings."""
from __future__ import annotations

import functools
import io
import json
import time

from bench import harness
from bench.tests import faults, tiny

SEED = 2 ** 31 + 101


def line(name: str, fault, monkeypatch) -> dict:
    """The result line of a tiny run of cell ``name``, the chip check
    skipped, with ``fault`` (or none) planted."""
    tiny.off_chip(monkeypatch)
    spec = tiny.cell(name)
    driver = harness.driver_of(spec)
    if fault is faults.altered_tokens:
        import repro.serving.engine as E
        monkeypatch.setattr(E, "make_serve_step", fault(E.make_serve_step))
    elif fault is not None:
        monkeypatch.setattr(driver, "program", functools.partial(
            driver.program, wrap=fault))
    out = io.StringIO()
    assert harness.run_cell(spec, SEED, 0.5, False, time.perf_counter(),
                            out=out, err=io.StringIO()) == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def control_readings(name: str, monkeypatch) -> dict:
    """One seed's calibration readings of cell ``name`` at a tiny size."""
    tiny.off_chip(monkeypatch)
    spec = tiny.cell(name)
    rows = list(harness.driver_of(spec).calibrate(
        spec, harness.reference_of(spec.config), [SEED]))
    return rows[0]
