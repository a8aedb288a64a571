#!/usr/bin/env python3
"""Readings of a cell's compared numbers over many seeds in one process:
the program's on ``--seeds`` and the control's on ``--control-seeds``
(the inputs the limits in ``traffic/<mix>.json`` are set from).

    python3 chipbench/calibrate.py --workload <name> --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 2

One JSON line per run on standard output.  Needs the cell's chips.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault (harness/faults.py) in every run")
    args = ap.parse_args(argv)
    found = runner.find_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    runner.configure_jax()
    try:
        devices = runner.require_devices(found["cell"]["chips"])
    except runner.NoAccelerator as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    sys.path.insert(0, os.path.join(runner.ROOT, "src"))
    if args.fault:
        from harness import faults
        faults.plant(found["traffic"]["driver"], args.fault)
    runs = [(int(s), False) for s in args.seeds.split(",") if s] + \
        [(int(s), True) for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        t0 = time.perf_counter()
        drv_log = []
        r = runner.run_cell(found, seed, args.seconds, False, devices, t0,
                            control=control, log=drv_log.append)
        readings = [m for m in drv_log if m.startswith("readings ")]
        print(json.dumps({
            "seed": seed, "control": control, "correct": r["correct"],
            "failed": r["failed"], "metrics": r["metrics"],
            "readings": json.loads(readings[-1][9:]) if readings else None,
            "run_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
