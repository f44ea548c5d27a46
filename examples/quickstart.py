"""Quickstart: one `FlashStore`, three backends (SSD simulator, JAX/Pallas
device table, multi-device sharded table) — same API, same deferred-update
discipline (H_R buffer → block-local merges).

Run: PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import FlashStore

enable_compile_cache()

rng = np.random.default_rng(0)
tokens = (rng.zipf(1.4, size=200_000) % (1 << 20)).astype(np.int64)
uniq, cnt = np.unique(tokens, return_counts=True)
probe, truth = uniq[:512], dict(zip(uniq.tolist(), cnt.tolist()))

for backend in ("sim", "device", "sharded"):
    with FlashStore.open(backend=backend, scheme="MDB-L") as store:
        store.update(tokens)                    # buffered + deduped in H_R
        store.increment(int(probe[0]), -1)      # deletion-by-decrement §2.6
        store.increment(int(probe[0]), +1)
        counts = store.query(probe)             # batched, read-your-writes
        ok = all(truth[int(k)] == int(c) for k, c in zip(probe, counts))
        store.flush()                           # durability point: merge
        wear = store.stats().get("tile_stores", store.stats().get("cleans"))
        print(f"{backend:8s} 512 point queries correct: {ok}; "
              f"wear (cleans analogue): {wear}")
