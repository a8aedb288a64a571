"""The training cell's check, driven on the CPU at a small size: a sound
run passes it; the float8 control and each planted fault fail it."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import pytest

from cellrun import run
from harness import faults

CELL = "train.node18.aca"


def test_sound_run_is_correct():
    r = run(CELL)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0


def test_control_is_not_correct():
    r = run(CELL, control=True)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_fault_is_not_correct(fault):
    undo = faults.plant("train", fault)
    try:
        r = run(CELL)
    finally:
        undo()
    assert not r["correct"], (fault, r["checks"])
