"""``correct`` on the CPU at a size a test can hold: a sound run passes, the
control (the reference in bfloat16 in the program's place) fails, and each
fault a training cell can have, planted under the timed path, fails."""

import pytest

import faults
from conftest import SMALL, VALID_CELL

from benchmark import run as bench_run


def drive(cell="higgs.fused-sort", **kw):
    return bench_run.run_cell(cell, 20260930, 1.0, 0, need_chip=False,
                              size_override=SMALL, extra_cells=[VALID_CELL],
                              **kw)


def failing(line):
    return sorted(k for k, c in line["checks"].items()
                  if not c["value"] <= c["limit"])


CELLS = ["higgs.fused-sort", "higgs.valid-sort"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    line = drive(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # named, whatever it is
    assert line["info"]["path"]["pipelined"] is (cell == "higgs.fused-sort")
    assert ("valid_auc_gap" in line["checks"]) is (cell == "higgs.valid-sort")


@pytest.mark.parametrize("control", ["bf16", "bf16_all"])
def test_control_is_not_correct(control):
    line = drive(control=control)
    assert not line["correct"]
    assert {"leaf_value_gap_weighted", "root_gain_gap"} <= set(failing(line))


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS
    for f in sorted(faults.VALID_FAULTS if "valid" in c else faults.FAULTS)])
def test_fault_under_the_timed_path_is_not_correct(cell, fault):
    with faults.VALID_FAULTS[fault]():
        line = drive(cell)
    assert not line["correct"], (fault, line["checks"])
    print(cell, fault, failing(line))
