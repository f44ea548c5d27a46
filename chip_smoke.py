"""Smoke run of FlashStore's device path on a TPU, at a deployment's size.

    python chip_smoke.py              # one chip: MB, MDB and MDB-L
    python chip_smoke.py --chips 4    # the sharded store over four chips

One chip: for each scheme, a ``FlashStore`` on the device backend with the
paper's Wiki geometry (``q_log2=24``, ``r_log2=10``: 16,777,216 slots,
128 MiB of keys and counts and 8 MiB of Bloom rows in HBM) ingests a Zipf
stream shaped like the Wiki corpus (7.1% of tokens distinct), flushes,
and answers 65,536 keys from the stream and 65,536 keys absent from it.
Every answer must equal a plain NumPy count of the same stream, no count
may be dropped, and the store's update and lookup programs must hold
compiled kernels (``tpu_custom_call``), not interpreted ones.

``--chips 4`` runs only the sharded store: a 4-device mesh, ``MDB-L``,
4 shards of ``q_log2=23`` (33.5M slots, 256 MiB) sized for the paper's
Meme corpus (4.2% distinct), the same exact-answer check, no entry carried
over by the collective, and one shard of the table on each device.

Each phase prints one JSON line (seconds per phase, peak device memory,
counts checked); the last line is ``{"ok": true, "device": {...}}``.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

WIKI_UNIQUE, MEME_UNIQUE = 0.071, 0.042   # distinct / total (paper §3.1)
N_PRESENT = N_ABSENT = 65_536
BATCH = 1 << 16                           # tokens per store.update call
# dispatch shapes: update chunks of 16,384 entries (the MDB-L log's size)
# dispatch a quarter as many programs as the default 4,096; at q_log2=24
# a merge costs about the same whatever its size (PERF.md), and the run
# must end within 20 minutes
CHUNK, QUERY_CHUNK = 1 << 14, 1024
MASK31 = (1 << 31) - 1
# one chip: the Wiki deployment, each scheme with its stream length
GEOMETRY = dict(q_log2=24, r_log2=10)
SCHEMES = (("MB", 4 << 20), ("MDB", 4 << 20), ("MDB-L", 16 << 20))
# four chips: per-shard geometry of the Meme deployment, stream length
SHARDED = dict(q_log2=23, r_log2=10, tokens=16 << 20)


def key_of(rank: np.ndarray, seed: int) -> np.ndarray:
    """Vocabulary rank → 31-bit key. Every step is a bijection of
    ``[0, 2**31)``, so distinct ranks give distinct keys, spread over the
    whole key space (never the reserved ``EMPTY = -1``)."""
    h = (rank.astype(np.uint64) + np.uint64(seed * 0x9E3779B1)) & MASK31
    for shift, mult in ((15, 0x2C1B3C6D), (12, 0x297A2D39), (15, 1)):
        h ^= h >> np.uint64(shift)
        h = (h * np.uint64(mult)) & MASK31
    return h.astype(np.int64)


def zipf_stream(n: int, unique_frac: float, seed: int):
    """``n`` tokens over a vocabulary of ``unique_frac * n`` keys: each
    key once, the rest drawn Zipf (s=1) by rank, shuffled. Returns
    ``(tokens, vocab_size)``; ranks ``>= vocab_size`` never occur."""
    rng = np.random.default_rng(seed)
    v = int(n * unique_frac)
    cdf = np.cumsum(1.0 / np.arange(1, v + 1))
    draws = np.searchsorted(cdf, rng.random(n - v) * cdf[-1])
    ranks = np.concatenate([np.arange(v), np.minimum(draws, v - 1)])
    return key_of(rng.permutation(ranks), seed), v


def probe_keys(vocab: int, seed: int):
    """65,536 distinct keys from the stream and 65,536 absent, shuffled."""
    rng = np.random.default_rng(seed + 1)
    present = rng.choice(vocab, N_PRESENT, replace=False)
    absent = vocab + rng.choice(vocab, N_ABSENT, replace=False)
    return key_of(rng.permutation(np.concatenate([present, absent])), seed)


def check_answers(tokens, keys, got) -> int:
    """Compare with ``np.unique`` counts of the stream; return #checked."""
    uniq, counts = np.unique(tokens, return_counts=True)
    pos = np.clip(np.searchsorted(uniq, keys), 0, uniq.size - 1)
    want = np.where(uniq[pos] == keys, counts[pos], 0)
    bad = np.flatnonzero(np.asarray(got) != want)
    if bad.size:
        raise AssertionError(
            f"{bad.size} of {keys.size} answers differ from the NumPy "
            f"count, e.g. key {keys[bad[0]]}: {got[bad[0]]} != {want[bad[0]]}")
    assert (want > 0).sum() == N_PRESENT, "probe keys not all present"
    return int(keys.size)


def ingest(store, tokens) -> float:
    t0 = time.perf_counter()
    for lo in range(0, tokens.size, BATCH):
        store.update(tokens[lo:lo + BATCH])
    return time.perf_counter() - t0


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def peak_bytes(dev) -> int | None:
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


def compiled_kernels(jax, tj, cfg, state) -> None:
    """The store's update and lookup programs, lowered as it dispatches
    them, must call the compiled kernels."""
    i32 = jax.ShapeDtypeStruct((CHUNK,), np.int32)
    q = jax.ShapeDtypeStruct((QUERY_CHUNK,), np.int32)
    for name, lowered in (("update", tj.update.lower(cfg, state, i32, i32)),
                          ("lookup", tj.lookup_ex.lower(cfg, state, q))):
        if "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(f"{name} program holds no compiled kernel")


def one_chip(jax, seed: int) -> None:
    from repro.core import FlashStore
    from repro.core import table_jax as tj
    dev = jax.devices()[0]
    for scheme, n_tokens in SCHEMES:
        tokens, vocab = zipf_stream(n_tokens, WIKI_UNIQUE, seed)
        keys = probe_keys(vocab, seed)
        opts = dict(backend="device", scheme=scheme, chunk=CHUNK,
                    query_chunk=QUERY_CHUNK, **GEOMETRY)
        # compile phase: the same programs on a small throwaway stream
        def warm():
            with FlashStore.open(**opts) as s:
                s.update(tokens[:BATCH])
                s.flush()
                s.query(keys[:2 * QUERY_CHUNK])
        _, compile_s = timed(warm)
        with FlashStore.open(**opts) as store:
            ingest_s = ingest(store, tokens)
            _, flush_s = timed(store.flush)
            got, query_s = timed(lambda: store.query(keys))
            checked = check_answers(tokens, keys, got)
            wear = store.wear()
            assert wear["dropped"] == 0, f"{wear['dropped']} counts dropped"
            compiled_kernels(jax, tj, store.cfg, store.state)
        print(json.dumps({
            "phase": scheme, "device_kind": dev.device_kind, **GEOMETRY,
            "tokens": int(tokens.size), "distinct": vocab,
            "compile_s": compile_s, "ingest_s": ingest_s,
            "flush_s": flush_s, "query_s": query_s,
            "peak_bytes_in_use": peak_bytes(dev), "checked": checked,
            "dropped": wear["dropped"], "tile_stores": wear["tile_stores"],
            "tpu_custom_call": True}), flush=True)


def four_chips(jax, seed: int) -> None:
    from repro.core import FlashStore
    devs = jax.devices()
    tokens, vocab = zipf_stream(SHARDED["tokens"], MEME_UNIQUE, seed)
    keys = probe_keys(vocab, seed)
    t0 = time.perf_counter()
    with FlashStore.open(backend="sharded", scheme="MDB-L",
                         q_log2=SHARDED["q_log2"], r_log2=SHARDED["r_log2"],
                         num_shards=len(devs), shard_chunk=CHUNK // 4,
                         query_chunk=QUERY_CHUNK) as store:
        open_s = time.perf_counter() - t0
        ingest_s = ingest(store, tokens)
        _, flush_s = timed(store.flush)
        got, query_s = timed(lambda: store.query(keys))
        checked = check_answers(tokens, keys, got)
        stats = store.stats()
        assert stats["write_carried"] == 0, "the collective carried entries"
        assert stats["dropped"] == 0, f"{stats['dropped']} counts dropped"
        shards = store.state.keys.addressable_shards
        holders = sorted(s.device.id for s in shards)
        if holders != sorted(d.id for d in devs):
            raise AssertionError(f"table shards sit on devices {holders}")
        shard_shape = shards[0].data.shape
    print(json.dumps({
        "phase": "sharded MDB-L", "device_kind": devs[0].device_kind,
        "num_shards": len(devs), "q_log2_per_shard": SHARDED["q_log2"],
        "r_log2": SHARDED["r_log2"],
        "tokens": int(tokens.size), "distinct": vocab,
        "open_s": open_s, "ingest_s": ingest_s, "flush_s": flush_s,
        "query_s": query_s, "checked": checked,
        "write_carried": stats["write_carried"], "dropped": stats["dropped"],
        "shard_devices": holders, "shard_shape": list(shard_shape),
        "peak_bytes_in_use": [peak_bytes(d) for d in devs]}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
              "this smoke run has no CPU fallback", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devs)}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    (four_chips if args.chips == 4 else one_chip)(jax, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
