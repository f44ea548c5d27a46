"""Benchmark suite entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig3*   — paper Figure 3 (query times)           bench_query_times
  fig3dev — per-key vs batched device query engine bench_query_times
  fig4*   — paper Figure 4 + §3.5 naive (I/O cost) bench_io_costs
  fig5*   — paper Figure 5 (cleans)                bench_cleans
  fig6dev — sharded FlashStore weak scaling        bench_weak_scaling
  fig7dev — continuous-batching serving traffic    bench_serving
  table2* — paper Table 2 (op mix)                 bench_block_page_ops
  kernel* — Pallas flash-hash microbench           bench_kernels
  roofline* — dry-run-derived roofline terms       bench_roofline

Run: ``PYTHONPATH=src python -m benchmarks.run [--only fig3,...]
[--smoke] [--json PATH]``

``--json PATH`` additionally writes the rows as machine-readable JSON
(name, us_per_call, parsed derived fields) — the artifact CI's
bench-smoke job uploads, and the format of the committed
``BENCH_PR*.json`` trajectory files. ``--smoke`` shrinks the workloads
for a minutes-long CI run. ``--baseline PATH`` compares the device
acceptance rows (fig3dev batched speedup, fig4dev engine-buffered
speedup) against their floors, printing the committed trajectory file's
values for reference, and exits nonzero on a regression — the CI
bench-smoke gate. ``--slow`` opts into the long-running fig4dev
change-segment-size and RAM-buffer-size sweeps (the paper's remaining
Figure-4 axes on device).
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

from . import (bench_block_page_ops, bench_cleans, bench_io_costs,
               bench_kernels, bench_query_times, bench_roofline,
               bench_serving, bench_weak_scaling)
from .common import (compare_to_baseline, emit, rows_to_json, set_slow,
                     set_smoke)
from repro.compile_cache import enable_compile_cache

SUITES = {
    "fig3": bench_query_times,
    "fig4": bench_io_costs,
    "fig5": bench_cleans,
    "fig6": bench_weak_scaling,
    "fig7": bench_serving,
    "table2": bench_block_page_ops,
    "kernel": bench_kernels,
    "roofline": bench_roofline,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names (default: all)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as machine-readable JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced workloads (CI bench-smoke job)")
    ap.add_argument("--slow", action="store_true",
                    help="include long-running sweeps (fig4dev change-"
                         "segment-size and RAM-buffer-size grids)")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="compare acceptance rows against this committed "
                         "BENCH_PR*.json; exit 1 if any speedup falls "
                         "below its floor")
    args = ap.parse_args()
    enable_compile_cache()
    if args.smoke:
        set_smoke()
    if args.slow:
        set_slow()
    names = list(SUITES) if not args.only else args.only.split(",")
    rows = []
    suite_secs = {}
    print("name,us_per_call,derived")
    try:
        for name in names:
            t0 = time.time()
            suite_rows = []
            SUITES[name].run(suite_rows)
            emit(suite_rows)
            rows.extend(suite_rows)
            suite_secs[name] = round(time.time() - t0, 1)
            print(f"# suite {name}: {len(suite_rows)} rows in "
                  f"{suite_secs[name]}s", file=sys.stderr, flush=True)
    finally:
        # write whatever completed even if a suite raised, so the CI
        # artifact always carries the rows gathered up to the failure
        if args.json:
            from .common import SMOKE_SCALE as scale  # set_smoke may run
            payload = rows_to_json(rows, meta={
                "suites": names,
                "suite_seconds": suite_secs,
                "smoke_scale": scale,
                "python": platform.python_version(),
                "platform": platform.platform(),
            })
            with open(args.json, "w") as f:
                json.dump(payload, f, indent=1)
                f.write("\n")
            print(f"# wrote {len(rows)} rows to {args.json}",
                  file=sys.stderr, flush=True)
    if args.baseline:
        if not compare_to_baseline(rows, args.baseline):
            sys.exit(1)


if __name__ == "__main__":
    main()
