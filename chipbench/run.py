#!/usr/bin/env python3
"""Benchmark of the NODE solver stack on a TPU: one run of one cell.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``, whose ``driver`` names the
generator in ``chipbench/drivers/``).  With ``--trace 0`` the last line
of standard output is the result with the cell's end-to-end metrics;
with ``--trace 1`` the window is traced and the per-layer metrics
(``chipbench/metrics/<name>.py``) are reported.  The compared numbers of
the correctness check are the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 3 and prints no result.  ``chipbench/calibrate.py``
reads the compared numbers over many seeds, of the program, of the
cell's control and of planted faults.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from harness import runner  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="write the trace's reduction to this directory")
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
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"workload {args.workload} seed {args.seed}")
    result = runner.run_cell(
        found, args.seed, args.seconds, bool(args.trace), devices, T_START,
        trace_dir=args.keep_trace, keep_trace=args.keep_trace is not None,
        log=log)
    runner.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
