"""Tiny versions of the benchmark's cells for CPU tests: the same
configuration and traffic files, shrunk to sizes the Pallas interpreter
runs in seconds."""
import time

import bench

# 16 blocks of 1,024 slots; a corpus whose vocabulary (10,000 keys)
# fits them, with counts large enough that the 33 hottest exceed int16
TABLE = dict(q_log2=14, r_log2=10, chunk=256, query_chunk=128,
             preload_chunk=2048, corpus_tokens=20_000_000,
             distinct_share=0.0005)
INGEST = dict(calls=2, tokens_per_call=1024, pool_groups=2,
              check_keys=1024)
LOOKUP = dict(batch=2048, pool_batches=4)


def cell(name: str):
    c = bench.load_cell(name)
    c.cfg = dict(c.cfg, **TABLE)
    c.traffic = dict(c.traffic,
                     **(INGEST if c.traffic["kind"] == "ingest" else LOOKUP))
    return c


def run(name: str, seed: int = 2**31 + 7, seconds: float = 0.0, **kw):
    """One run of the tiny cell; ``seconds=0`` feeds exactly one group
    (or one batch) after warm-up, so the work is the same every time."""
    return bench.run_cell(cell(name), seed, seconds, False,
                          time.perf_counter(), require_chip=False, **kw)
