"""A run with the timed path broken underneath comes out not correct:
once for each fault the cell can have (see chipbench/faults.py).  One
chip has no exchange between chips to leave out."""
import pytest

from chipbench import faults
from conftest import drive, tiny_cell

CASES = [("w8a.train", "unchanged"), ("w8a.train", "half_batch"),
         ("covtype.train", "unchanged"), ("covtype.train", "half_batch"),
         ("w8a.score", "altered"),
         ("w8a.live", "unchanged"), ("w8a.live", "half_batch"),
         ("w8a.live", "altered")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(name, fault):
    cell = tiny_cell(name)
    r = drive(cell, driver=faults.driver(cell.kind, fault))
    assert r["correct"] is False
    failing = [k for k, c in r["checks"].items() if not c["value"] <= c["limit"]]
    assert failing, r["checks"]
