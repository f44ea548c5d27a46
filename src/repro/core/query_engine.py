"""Host-side batched query engine for the device flash-hash table.

The paper's query axis (§2.7, Figure 3) measures consolidation cost:
every point query must combine the data segment, the change segment and
the overflow region. Serving that one key at a time pays a full jitted
dispatch — data-segment probe plus whole change-segment scan — per key.
This engine is the batched front door every consumer (TF-IDF, corpus
stats, the serving prefix cache) goes through instead:

* **dedup before dispatch** — duplicate keys in a batch resolve to one
  device probe (``np.unique``), then fan back out to their positions;
* **fixed-shape padded chunks** — misses are EMPTY-padded up to
  ``chunk`` so every table sees exactly one compiled lookup program,
  regardless of batch size;
* **hot-key cache** — a small host dict in front of the device table.
  Counts are global aggregates, so *any* update/merge/flush may move any
  key's count: writers call :meth:`invalidate` (wholesale clear) after
  every mutation rather than tracking per-key dirtiness (DESIGN.md §6);
* **invalidate fencing** — drains run on a background worker thread
  since the store went async (DESIGN.md §9), so an invalidation can land
  while a batch lookup is mid-flight. Every ``invalidate()`` bumps an
  epoch; a lookup only populates the cache if the epoch it started under
  is still current, so a count probed against a pre-drain state can
  never be cached after the drain's invalidation (it would be served
  stale forever);
* **filter-backed negative verdicts** (DESIGN.md §12) — when the table
  carries blocked-Bloom filters, one cheap ``filter_fn`` dispatch tests
  the whole miss set first: definite misses answer 0 with *no* lookup
  dispatch at all (skipping the tile probe *and* the change-segment /
  overflow scans) and enter the hot cache as negative entries under the
  same epoch fence, so a concurrent drain evicts them exactly like
  positive entries;
* **probe-distance aggregation** — per-key probe distances from the
  device are folded into batch-level wear/latency stats (sum + max +
  served-query count); cache hits do not re-probe and add nothing.

The engine is deliberately state-free with respect to the table: callers
pass the current ``DeviceTableState`` to :meth:`query_batch`, so
functional state updates (``state -> op -> state``) stay outside.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np

from .spans import span


@dataclasses.dataclass
class QueryEngineStats:
    """Batch-aggregated query-path counters (DESIGN.md §6)."""

    batches: int = 0            # query_batch calls
    keys: int = 0               # keys requested (incl. duplicates)
    unique_keys: int = 0        # after dedup
    cache_hits: int = 0         # unique keys served from the hot cache
    device_queries: int = 0     # unique keys sent to the device
    device_dispatches: int = 0  # compiled lookup launches (chunks)
    invalidations: int = 0      # hot-cache clears by writers
    fenced: int = 0             # cache inserts dropped because a writer
                                # invalidated while the lookup was in
                                # flight (epoch fence, DESIGN.md §9)
    probe_total: int = 0        # sum of device probe distances
    probe_max: int = 0          # worst single probe in any batch
    filter_negatives: int = 0   # unique keys answered 0 by the Bloom
                                # pre-filter with no lookup dispatch (§12)
    tile_loads: int = 0         # data-segment tiles fetched by dispatched
                                # lookups (when the lookup_fn reports them;
                                # true negatives contribute 0)

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


class BatchedQueryEngine:
    """Dedup + chunk + hot-cache front end over ``table_jax.lookup``."""

    def __init__(self, cfg, chunk: int = 1024, hot_capacity: int = 4096,
                 lookup_fn=None, filter_fn=None):
        import jax.numpy as jnp  # deferred: sim-only users stay jax-free

        from . import table_jax as tj
        self._jnp = jnp
        self._tj = tj
        self.cfg = cfg
        self.chunk = int(chunk)
        self.hot_capacity = int(hot_capacity)
        # pluggable device dispatch: any (state, keys) -> (counts, dists)
        # or (counts, dists, tile_loads) with table_jax.lookup's contract
        # (EMPTY -> (0, 0)). The sharded backend passes its shard_map'd
        # consolidated lookup here; the default is the single-table path,
        # which reports tile loads.
        self._lookup = (lookup_fn if lookup_fn is not None
                        else lambda state, q: tj.lookup_ex(self.cfg,
                                                           state, q))
        # optional Bloom pre-filter: (state, keys) -> bool/int may-contain
        # mask (False ⇒ definitively absent from the whole device table).
        # The store wires table_jax.filter_probe (or the sharded psum'd
        # twin) here when cfg.filters is on.
        self._filter = filter_fn
        self._hot: Dict[int, int] = {}
        # invalidation epoch: bumped on every invalidate(). Lookups fence
        # their cache inserts on it so a count probed against a pre-drain
        # state is never remembered after the drain invalidated.
        self._epoch = 0
        # opt-in happens-before recorder (analysis.race_harness.attach)
        self.tracer = None
        self.stats = QueryEngineStats()

    def _trace(self, kind: str, resource=None, rw=None, **meta) -> None:
        if self.tracer is not None:
            self.tracer.record(kind, resource=resource, rw=rw, **meta)

    # -- cache maintenance --------------------------------------------------
    def invalidate(self) -> None:
        """Writers call this after any update/merge/flush: counts are
        global aggregates, so the whole hot cache goes at once. Also
        bumps the epoch fence — a lookup racing this call will drop its
        (now possibly stale) cache inserts."""
        self._epoch += 1
        self._trace("invalidate", "cache", "w", epoch=self._epoch)
        if self._hot:
            self._hot.clear()
            self.stats.invalidations += 1

    def _remember(self, key: int, count: int) -> None:
        if self.hot_capacity <= 0:
            return  # cache disabled
        if len(self._hot) >= self.hot_capacity and key not in self._hot:
            # FIFO eviction via dict insertion order — cheap, and good
            # enough for a cache that is cleared on every table write.
            self._hot.pop(next(iter(self._hot)))
        self._hot[key] = count

    # -- the batched read path ---------------------------------------------
    def query_batch(self, state, keys) -> np.ndarray:
        """Counts for ``keys`` (any shape, flattened) against ``state``.

        Returns an int64 array aligned with the flattened input;
        duplicate keys share one probe, ``EMPTY`` keys return 0.
        """
        jnp, tj = self._jnp, self._tj
        flat = np.asarray(keys).reshape(-1).astype(np.int64)
        self.stats.batches += 1
        self.stats.keys += flat.size
        if flat.size == 0:
            return np.zeros(0, np.int64)
        with span("query.dedup"):
            uniq, inv = np.unique(flat, return_inverse=True)
            self.stats.unique_keys += uniq.size
            ucnt = np.zeros(uniq.size, np.int64)
            if not self._hot:
                # cold cache (the steady state under interleaved writes):
                # skip the per-key probe loop entirely
                miss_idx = np.flatnonzero(uniq != tj.EMPTY).tolist()
            else:
                self._trace("cache_read", "cache", "r")
                miss_idx = []
                for i, k in enumerate(uniq):
                    if k == tj.EMPTY:
                        continue  # padding key: count 0, never probed or cached
                    c = self._hot.get(int(k))
                    if c is None:
                        miss_idx.append(i)
                    else:
                        ucnt[i] = c
                        self.stats.cache_hits += 1
        if miss_idx:
            epoch = self._epoch          # fence: inserts only if unchanged
            self._trace("lookup_begin", "state", "r", epoch=epoch)
            miss = uniq[miss_idx]
            if self._filter is not None and miss.size:
                # Bloom pre-pass (DESIGN.md §12): one cheap dispatch over
                # the whole miss set. False ⇒ the key is in none of data /
                # change / overflow, so the entire lookup is skipped —
                # ucnt already holds 0 for those positions.
                step = self.chunk
                may = np.empty(miss.size, bool)
                with span("query.filter"):
                    for lo in range(0, miss.size, step):
                        part = miss[lo:lo + step]
                        pad = step - part.size
                        if pad:
                            part = np.concatenate(
                                [part, np.full(pad, tj.EMPTY, np.int64)])
                        m = np.asarray(self._filter(
                            state, jnp.asarray(part, jnp.int32)))
                        may[lo:lo + step - pad] = m[:step - pad].astype(bool)
                neg = miss[~may]
                if neg.size:
                    self.stats.filter_negatives += neg.size
                    if epoch == self._epoch:
                        # negative entries are ordinary count-0 entries:
                        # the next invalidate() evicts them wholesale
                        self._trace("cache_insert", "cache", "w",
                                    epoch=epoch)
                        with span("query.remember"):
                            for k in neg:
                                self._remember(int(k), 0)
                    else:
                        self._trace("lookup_fenced", epoch=self._epoch)
                        self.stats.fenced += neg.size
                    keep = np.flatnonzero(may)
                    miss_idx = [miss_idx[i] for i in keep]
                    miss = miss[may]
            self.stats.device_queries += miss.size
            got = np.empty(miss.size, np.int64)
            step = self.chunk
            for lo in range(0, miss.size, step):
                part = miss[lo:lo + step]
                pad = step - part.size
                if pad:  # fixed shapes → one compiled program per table
                    part = np.concatenate(
                        [part, np.full(pad, tj.EMPTY, np.int64)])
                n_real = step - pad
                with span("query.lookup"):
                    res = self._lookup(state, jnp.asarray(part, jnp.int32))
                    cnt, dist = res[0], res[1]
                    if len(res) == 3:
                        # scalar (single table) or per-shard vector
                        self.stats.tile_loads += int(
                            np.asarray(res[2]).sum())
                    cnt = np.asarray(cnt)[:n_real]
                    dist = np.asarray(dist)[:n_real]
                got[lo:lo + n_real] = cnt
                self.stats.device_dispatches += 1
                self.stats.probe_total += int(dist.sum())
                if dist.size:
                    self.stats.probe_max = max(self.stats.probe_max,
                                               int(dist.max()))
            ucnt[miss_idx] = got
            if epoch == self._epoch:
                self._trace("cache_insert", "cache", "w", epoch=epoch)
                with span("query.remember"):
                    for k, c in zip(miss, got):
                        self._remember(int(k), int(c))
            else:
                # a drain invalidated mid-lookup: these counts may predate
                # it, so they must not outlive the invalidation
                self._trace("lookup_fenced", epoch=self._epoch)
                self.stats.fenced += miss.size
        return ucnt[inv]

    def query(self, state, key: int) -> int:
        """Single-key convenience wrapper (one-element batch)."""
        return int(self.query_batch(state, np.asarray([key]))[0])
