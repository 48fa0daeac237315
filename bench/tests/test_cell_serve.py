"""The serving cell at a tiny size: a sound run is correct, a run whose
decode step alters every token it produces is not, and the control's
first choices (the reference with fp8 matmuls) read a wider gap than the
program's served tokens."""
import pytest

from bench.tests import cellrun, faults

CELL = "qwen3-1.7b.serve-chat"


@pytest.mark.parametrize("fault", [None, faults.altered_tokens],
                         ids=lambda f: getattr(f, "__name__", "sound"))
def test_run_is_correct_only_when_sound(fault, monkeypatch):
    line = cellrun.line(CELL, fault, monkeypatch)
    assert line["correct"] is (fault is None), line["checks"]


def test_the_control_reads_wider_than_the_program(monkeypatch):
    row = cellrun.control_readings(CELL, monkeypatch)
    assert row["control"]["gap"] > 3 * row["program"]["gap"], row
