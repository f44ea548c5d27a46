"""Run one benchmark cell of FlashStore on the accelerator and print its result.

    python3 flashbench/run.py --workload wiki-mdbl.ingest --seed 7 \\
        --seconds 30 --trace 0

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``flashbench/bench.py``). With ``--trace 0`` the
result holds the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from the engines' counters and a profiler trace
of the same window. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
with ``--trace 1`` a ``breakdown``); the numbers the correctness check
compared, each with its limit, come last in it under ``checks``, and as
the last lines of standard error. Without an accelerator, or with fewer
chips than the cell needs, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
# the TPU runtime would otherwise log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import bench
    cell = bench.load_cell(args.workload)
    bench.enable_compile_cache()
    out = bench.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                         T_START)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
