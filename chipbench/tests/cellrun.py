"""Drive a whole run of a cell on the CPU at a small size, past the
harness's look for a chip, for the control and fault tests."""

import benchpaths  # noqa: F401  (first: puts the harness on the path)

import copy
import time

import jax

from harness import runner

SMALL = {
    "solve.mlp256.heavy": dict(traffic=dict(rows=16, ref_block_rows=8,
                                            logk_span=2.0),
                               config=dict(max_steps=64)),
    # 8 rows on each of 4 chips
    "solve.mlp256.heavy.4chip": dict(traffic=dict(rows=32, ref_block_rows=8,
                                                  logk_span=2.0),
                                     config=dict(max_steps=64)),
    "train.node18.aca": dict(traffic=dict(seq=32, batch=4),
                             model=dict(n_layers=3, d_model=64, n_heads=4,
                                        n_kv_heads=4, head_dim=16, d_ff=160,
                                        vocab=512)),
}


def small_cell(workload):
    found = copy.deepcopy(runner.find_cell(workload))
    over = SMALL[workload]
    found["traffic"].update(over.get("traffic", {}))
    found["config"].update(over.get("config", {}))
    if "model" in over:
        found["config"]["model"].update(over["model"])
    return found


SEED = 2 ** 40 + 5


def run(workload, seed=SEED, seconds=0.5, control=False):
    found = small_cell(workload)
    chips = found["cell"]["chips"]
    return runner.run_cell(found, seed, seconds, False,
                           jax.devices()[:chips],
                           time.perf_counter(), control=control,
                           log=lambda *_: None)
