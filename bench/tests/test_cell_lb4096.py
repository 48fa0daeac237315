"""The vision training cell ``resnet44.lb4096-gbn`` at a tiny size: a sound run
is correct; a run whose step returns its state unchanged, or leaves half of
its batch out, is not; and the control (the reference in bfloat16 in the
program's place) fails the cell's limits."""
import pytest

from bench.tests import cellrun, faults

CELLS = ["resnet44.lb4096-gbn"]
FAULTS = [None, faults.state_unchanged, faults.half_batch_vision]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: getattr(
    f, "__name__", "sound"))
@pytest.mark.parametrize("name", CELLS)
def test_run_is_correct_only_when_sound(name, fault, monkeypatch):
    line = cellrun.line(name, fault, monkeypatch)
    assert line["correct"] is (fault is None), line["checks"]


@pytest.mark.parametrize("name", CELLS)
def test_the_control_fails_the_limits(name, monkeypatch):
    row = cellrun.control_readings(name, monkeypatch)
    limits = cellrun.tiny.cell(name).traffic["limits"]
    assert all(row["program"][k] <= limits[k] for k in limits)
    assert any(row["control"][k] > limits[k] for k in limits), row
