"""Serving quickstart: continuous batching over the paged prefix-KV
block pool (counting flash-hash refcounts as the page table), driven by
a tiny Zipf user trace on the sim backend.

Run: PYTHONPATH=src python examples/serve_quickstart.py
"""
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import model as M
from repro.serving import (ContinuousBatchingScheduler, PrefixKVCache,
                           make_trace, replay_trace)

enable_compile_cache()
cfg = dataclasses.replace(get_config("llama32_3b", tiny=True),
                          dtype="float32")
params = M.init_params(jax.random.PRNGKey(0), cfg)

cache = PrefixKVCache(block_tokens=16, capacity_blocks=64, backend="sim")
sched = ContinuousBatchingScheduler(cfg, params, prefix_cache=cache,
                                    max_slots=4, max_context=96)
trace = make_trace(num_requests=12, num_users=3, prefix_blocks=2,
                   max_new_tokens=8, vocab_size=cfg.vocab_size, seed=0)
report = replay_trace(sched, trace, workers=2)

print(report.summary())
s = cache.stats()
print(f"blocks resident={s['resident']} pool_high_water="
      f"{s['pool_high_water']} refcount_evictions={s['evictions']}")
