"""The training cell's check, driven on the CPU at a small size: a sound
run passes it; the float8 control and each planted fault fail it."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import os

import jax
import numpy as np
import pytest

import repro.train
from cellrun import SEED, run, small_cell
from harness import faults, runner

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


def _leaves_equal(a, b):
    la, ta = jax.tree.flatten(a)
    lb, tb = jax.tree.flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y)


@pytest.mark.parametrize("replay_from", ["copy", "seed"])
def test_replay_puts_back_its_state_bit_for_bit(replay_from, monkeypatch):
    """``"copy"`` puts back the state of the end of set-up; ``"seed"``
    the state set-up started from, remade from the seed; and the window
    starts from it."""
    started = {}

    class Recording(repro.train.TrainLoop):
        def __init__(self, model, opt, cfg, state, **kw):
            started["state"] = jax.device_get(state)
            super().__init__(model, opt, cfg, state, **kw)

    monkeypatch.setattr(repro.train, "TrainLoop", Recording)
    found = small_cell(CELL)
    found["traffic"]["replay_from"] = replay_from
    drv = runner.load_module(os.path.join(
        runner.BENCH_DIR, "drivers", "train.py")).Driver(
            found["config"], found["traffic"], SEED, jax.devices()[:1])
    drv.setup()
    end_of_setup = jax.device_get(drv.loop.state)
    want = end_of_setup if replay_from == "copy" else started["state"]
    assert int(want.step) == (found["traffic"]["check_steps"]
                              if replay_from == "copy" else 0)
    _leaves_equal(jax.device_get(drv.replay_start()), want)
    # a window of one step: from the seed it restores first, and runs
    # step 0; from the copy it runs on from the end of set-up
    out = drv.window(1e-3)
    assert out["attempted"] == 1
    assert drv.loop.step == int(want.step) + 1
    drv.free()


def test_unknown_replay_from_is_an_error():
    found = small_cell(CELL)
    found["traffic"]["replay_from"] = "disk"
    mod = runner.load_module(os.path.join(
        runner.BENCH_DIR, "drivers", "train.py"))
    with pytest.raises(ValueError):
        mod.Driver(found["config"], found["traffic"], SEED,
                   jax.devices()[:1])
