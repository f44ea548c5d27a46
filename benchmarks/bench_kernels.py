"""Flash-hash kernel microbench (beyond paper): merge/query throughput of
the device table vs the jnp reference path.

On the CPU the kernels run in the Pallas interpreter, so wall-times there
say nothing about a TPU; on a TPU they are the compiled kernels'. The
derived column carries the structural quantities that matter for the TPU
roofline: VMEM tile residency, bytes per merge, updates per tile.
"""
from __future__ import annotations

import time

import numpy as np

from .common import emit

import jax.numpy as jnp  # noqa: E402

from repro.core.hashing import Pow2Hash, filter_words_for  # noqa: E402
from repro.kernels.flash_hash import ops, ref  # noqa: E402


def _bench(fn, *args, iters=3):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(iters):
        r = fn(*args)
    for leaf in (r if isinstance(r, tuple) else (r,)):
        leaf.block_until_ready()
    return (time.time() - t0) / iters


def run(rows):
    pair = Pow2Hash(q_log2=16, r_log2=10)
    n_b, r = pair.num_slots, pair.r
    rng = np.random.default_rng(0)
    tk = jnp.full((n_b, r), ref.EMPTY, jnp.int32)
    tc = jnp.zeros((n_b, r), jnp.int32)
    toks = jnp.asarray(rng.integers(0, 1 << 20, size=1 << 14), jnp.int32)
    keys, cnts = ops.accumulate(toks)
    uk, uc, *_ = ops.bucket_updates(pair, keys, cnts, 512)
    tf = jnp.zeros((n_b, filter_words_for(r)), jnp.uint32)

    t_acc = _bench(ops.accumulate, toks)
    rows.append(("kernel/accumulate_16k", t_acc * 1e6,
                 "tokens=16384;dedup=sort+segsum"))
    t_ref = _bench(lambda: ref.merge_ref(pair, tk, tc, uk, uc))
    t_k = _bench(lambda: ops.merge(pair, tk, tc, tf, uk, uc))
    tile_bytes = r * 8  # keys+counts int32
    upd_bytes = 512 * 8
    rows.append(("kernel/merge_ref_jnp", t_ref * 1e6,
                 f"blocks={n_b};tile_B={tile_bytes};upd_B={upd_bytes}"))
    rows.append(("kernel/merge_pallas", t_k * 1e6,
                 f"blocks={n_b};vmem_per_tile_B={tile_bytes + upd_bytes};"
                 f"hbm_per_merge_B={n_b * (2 * tile_bytes + upd_bytes)}"))
    # dirty-block merge: grid over only n_d dirty tiles (the MDB / MDB-L
    # partial-merge path) — HBM traffic scales with the dirty fraction.
    for n_d in (1, n_b // 8, n_b):
        dirty = jnp.arange(n_d, dtype=jnp.int32)
        duk, duc = uk[:n_d], uc[:n_d]
        t_d = _bench(lambda: ops.merge_dirty(pair, tk, tc, tf, dirty,
                                             duk, duc))
        rows.append((f"kernel/merge_dirty_{n_d}of{n_b}", t_d * 1e6,
                     f"dirty={n_d};blocks={n_b};"
                     f"hbm_per_merge_B={n_d * (2 * tile_bytes + upd_bytes)}"))
    mk, mc, *_ = ops.merge(pair, tk, tc, tf, uk, uc)
    q = jnp.asarray(rng.integers(0, 1 << 20, size=2048), jnp.int32)
    t_q = _bench(lambda: ops.query_sorted(pair, mk, mc, q))
    rows.append(("kernel/query_2048_pallas", t_q * 1e6,
                 "queries=2048;tile_reuse=sorted"))
    t_qr = _bench(lambda: ref.query_ref(pair, mk, mc, q))
    rows.append(("kernel/query_2048_ref_jnp", t_qr * 1e6, "oracle"))
    return rows


if __name__ == "__main__":
    rows = []
    run(rows)
    emit(rows)
