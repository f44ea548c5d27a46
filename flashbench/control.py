"""The control of the correctness check: the reference in lower precision.

    python3 flashbench/control.py --workload wiki-mdbl.ingest \\
        --seconds 10 --seeds 11 12 13

The configurations state exact int32 counts. The control puts the plain
reference in the program's place, with its counts held in int16 (the
nearest precision below, the step that would tempt a change to pack the
counts): a full run of the cell on the chip at its own size, its window
included, whose compared answers are the reference's int16 counts
instead of the program's. The check must find it not correct. Prints one
JSON line per seed with the numbers compared.

The benchmark's own runs never run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))
# the TPU runtime would otherwise log to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

CONTROL_DTYPE = np.int16


def control_answers(store, corpus, ref, win, traffic):
    """The reference's counts in int16, for the ranks the run compares."""
    import bench
    _, ranks = bench.produced(store, corpus, ref, win, traffic)
    return ref.counts(ranks, CONTROL_DTYPE), ranks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import bench
    cell = bench.load_cell(args.workload)
    bench.enable_compile_cache()
    for seed in args.seeds:
        out = bench.run_cell(cell, seed, args.seconds, False,
                             time.perf_counter(), answers=control_answers)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": "reference, int16 counts",
                          "correct": out["correct"],
                          "checked": out["checked"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
