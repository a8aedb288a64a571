"""The readers of the ``shard_map`` layer and the whole-call share of
the peak over several chips, on a recorded trace of 4 device planes:
chips 0-3 busy 700, 900, 560 and 1000 ns of a 1000 ns window, with
100 + 100 + (20 + 40) ns of collectives."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import json
import os

import pytest

from harness import layers, runner, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def _records():
    with open(os.path.join(HERE, "fixtures", "trace_4dev.json")) as fh:
        return json.load(fh)


def _reader(name):
    return runner.load_module(os.path.join(
        runner.BENCH_DIR, "metrics", name + ".py")).read


def _ctx(records, chips):
    return {"summary": trace.reduce(records), "devices": [None] * chips,
            "counters": {"row_trials": 1000, "width": 256},
            "peaks": {"bf16_flops_per_s": 1e9}}


def test_collective_share_over_four_chips():
    ctx = _ctx(_records(), 4)
    assert ctx["summary"].busy_s == {0: pytest.approx(700e-9),
                                     1: pytest.approx(900e-9),
                                     2: pytest.approx(560e-9),
                                     3: pytest.approx(1000e-9)}
    assert _reader("collective_share.solve4")(ctx) == pytest.approx(
        100.0 * 260 / 3160)


def test_straggler_idle_over_four_chips():
    ctx = _ctx(_records(), 4)
    # (300 + 100 + 440 + 0) / 4 chips / 1000 ns
    assert _reader("straggler_idle.solve4")(ctx) == pytest.approx(21.0)


def test_straggler_idle_counts_a_chip_that_ran_nothing():
    recs = [r for r in _records() if r["dev"] != 1]
    # (300 + 1000 + 440 + 0) / 4 / 1000
    assert _reader("straggler_idle.solve4")(_ctx(recs, 4)) \
        == pytest.approx(43.5)


def test_one_chip_reads_no_straggler_wait():
    recs = [r for r in _records() if r["dev"] in (-1, 0)]
    assert _reader("straggler_idle.solve4")(_ctx(recs, 1)) == 0.0


@pytest.mark.parametrize("op, want", [
    ("all-reduce.128", True), ("all-reduce-start.3", True),
    ("all-reduce-done.3", True), ("all-gather.1", True),
    ("reduce-scatter.2", True), ("collective-permute-done.4", True),
    ("all-to-all.5", True), ("fusion.3", False),
    ("body.5[tpu_custom_call]", False), ("all-reduce-fusion.1", False),
])
def test_collectives_by_instruction_name(op, want):
    assert layers.is_collective(op) is want


def test_mfu_divides_by_every_chip_of_the_cell():
    read = _reader("mfu.solve")
    one = [r for r in _records() if r["dev"] in (-1, 0)]
    # the same work in the same window: 2 * 255**2 FLOPs a stage
    # evaluation, 6 per trial, 1000 trials, over 1 us at 1 GFLOP/s
    assert read(_ctx(one, 1)) == pytest.approx(100.0 * 6 * 2 * 255 ** 2
                                               * 1000 / 1e3)
    assert read(_ctx(_records(), 4)) == pytest.approx(
        read(_ctx(one, 1)) / 4)
