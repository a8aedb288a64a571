"""Benchmark driver — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Default is quick mode (CPU-friendly sizes); ``--full`` uses the larger
settings.  Output: ``name,value,derived`` CSV rows.
"""

from __future__ import annotations

import argparse
import time
import traceback

from . import (bench_batched_solve, bench_classification,
               bench_dense_eval, bench_failure_overhead,
               bench_mali_memory, bench_memory, bench_method_costs,
               bench_node_lm, bench_reliability, bench_reverse_error,
               bench_serve_node, bench_sharded_solve,
               bench_solver_robustness, bench_threebody,
               bench_timeseries, bench_toy_gradient)
from repro.compile_cache import enable_compile_cache

from .common import emit

BENCHES = [
    ("toy_gradient (Fig.6)", bench_toy_gradient.run),
    ("reverse_error (Fig.4/5)", bench_reverse_error.run),
    ("method_costs (Table 1)", bench_method_costs.run),
    ("classification (Table 2/Fig.7)", bench_classification.run),
    ("reliability (Table 3)", bench_reliability.run),
    ("solver_robustness (Tables 6/7)", bench_solver_robustness.run),
    ("timeseries (Table 4)", bench_timeseries.run),
    ("threebody (Table 5/Fig.8)", bench_threebody.run),
    ("node_lm (beyond-paper: LM ablation)", bench_node_lm.run),
    ("batched_solve (beyond-paper: batch_axis)", bench_batched_solve.run),
    ("memory (beyond-paper: segmented ACA)", bench_memory.run),
    ("dense_eval (beyond-paper: interpolate_ts)", bench_dense_eval.run),
    ("mali_memory (beyond-paper: reversible MALI)", bench_mali_memory.run),
    ("failure_overhead (solve-health guard gate)",
     bench_failure_overhead.run),
    ("sharded_solve (beyond-paper: mesh scaling)",
     bench_sharded_solve.run),
    ("serve_node (beyond-paper: continuous batching)",
     bench_serve_node.run),
]


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    failed = []
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.monotonic()
        try:
            fn(quick=not args.full)
            emit(f"bench_runtime_s/{name.split(' ')[0]}",
                 f"{time.monotonic() - t0:.1f}", "")
        except Exception:
            # per-bench isolation: one crashing bench reports and the
            # suite continues; the summary + exit code carry the failure
            failed.append(name)
            traceback.print_exc()
            emit(f"bench_failed/{name.split(' ')[0]}", "1", "")
    if failed:
        print(f"# {len(failed)} benchmark(s) failed: "
              + ", ".join(failed), flush=True)
        raise SystemExit(f"{len(failed)} benchmarks failed: "
                         + ", ".join(n.split(" ")[0] for n in failed))


if __name__ == "__main__":
    main()
