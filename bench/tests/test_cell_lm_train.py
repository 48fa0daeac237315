"""The language-model training cell at a tiny size: a sound run is correct;
a run whose step returns its state unchanged, or leaves half of its rows
out, is not; and the control (the reference with fp8 matmuls in the
program's place) fails the cell's limits."""
import pytest

from bench.tests import cellrun, faults

CELL = "qwen3-1.7b.train-8x2048"


@pytest.mark.parametrize("fault", [None, faults.state_unchanged,
                                   faults.half_batch_lm],
                         ids=lambda f: getattr(f, "__name__", "sound"))
def test_run_is_correct_only_when_sound(fault, monkeypatch):
    line = cellrun.line(CELL, fault, monkeypatch)
    assert line["correct"] is (fault is None), line["checks"]


def test_the_control_fails_the_limits(monkeypatch):
    row = cellrun.control_readings(CELL, monkeypatch)
    limits = cellrun.tiny.cell(CELL).traffic["limits"]
    assert all(row["program"][k] <= limits[k] for k in limits)
    assert any(row["control"][k] > limits[k] for k in limits), row
