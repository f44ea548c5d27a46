"""fig6dev (beyond paper): weak scaling of the sharded FlashStore.

The ROADMAP "distributed sharded table at scale" benchmark: the PR-4
facade fronts :mod:`repro.core.distributed` with per-shard H_R
partitions, shard-local flush thresholds and consolidated cross-shard
lookups; this suite measures whether throughput holds as the mesh grows
1 → 8 shards at **fixed per-shard load** (weak scaling, 8 virtual CPU
devices).

The multi-device XLA view must exist before jax initializes, so the
measurement runs in a subprocess (``weak_scaling_main.py``, mirroring
``tests/helpers/dist_*_main.py``) and this module parses its
``ROW|name|us|derived`` lines into suite rows. Note the virtual devices
share one physical CPU: ``weak_efficiency`` reflects the *software*
overhead of sharding (collective + per-shard bookkeeping), not real
multi-chip bandwidth. It is a CPU study only: a process that already
holds an accelerator would leave the chip to a child that cannot have
it, so :func:`run` refuses to start there. The sharded store's path on
real chips is ``python chip_smoke.py --chips 4``.
"""
from __future__ import annotations

import subprocess
import sys
from pathlib import Path

from .common import emit, smoke

HELPER = Path(__file__).resolve().parent / "weak_scaling_main.py"


def run(rows):
    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "fig6dev measures sharding overhead on virtual CPU devices in "
            f"a child process; this process runs on {jax.default_backend()}"
            ", and the chip belongs to one process at a time. Run the "
            "sharded store on the chip with `python chip_smoke.py "
            "--chips 4` instead.")
    cmd = [sys.executable, str(HELPER)] + (["--smoke"] if smoke() else [])
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=1800)
    if r.returncode != 0:
        raise RuntimeError(
            f"weak-scaling helper failed:\n{r.stdout[-2000:]}"
            f"\n{r.stderr[-4000:]}")
    parsed = 0
    for line in r.stdout.splitlines():
        if not line.startswith("ROW|"):
            continue
        _tag, name, us, derived = line.split("|", 3)
        rows.append((name, float(us), derived))
        parsed += 1
    if parsed == 0:
        raise RuntimeError(f"no ROW lines from helper:\n{r.stdout[-2000:]}")
    return rows


if __name__ == "__main__":
    rows = []
    run(rows)
    emit(rows)
