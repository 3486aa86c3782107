"""The control, the plain reference one precision step below the
configuration's in the program's place, comes out not correct on a
number it changes (see chipbench/faults.py)."""
import pytest

from chipbench import faults, harness
from conftest import drive, tiny_cell

#: the control's widest gap grows with the distinct rows it scores: the
#: configuration's whole pool of rows, and enough requests, so that it
#: reads as it does on the chip
CONTROL_RATE = 2000.0


@pytest.mark.parametrize("name", ["w8a.score", "w8a.live"])
def test_control_is_not_correct(name):
    cell = tiny_cell(name)
    cell.config["n"] = harness.load_cell(name).config["n"]
    cell.traffic["rate_per_s"] = CONTROL_RATE
    r = drive(cell, driver=faults.control(cell.kind))
    assert r["correct"] is False
    gap = r["checks"]["score_gap"]
    assert gap["value"] > gap["limit"], r["checks"]


@pytest.mark.parametrize("name", ["w8a.train", "covtype.train"])
def test_training_control_is_not_correct(name):
    cell = tiny_cell(name)
    r = drive(cell, driver=faults.control(cell.kind))
    assert r["correct"] is False
    gap = r["checks"]["model_gap"]
    assert gap["value"] > gap["limit"], r["checks"]
