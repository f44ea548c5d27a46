"""Host-side batched write engine for the device flash-hash table.

The paper's insert/update axis (§2.2, Figure 4) is won by buffering and
batching writes *before* they reach the device: the RAM buffer H_R
absorbs and dedups the raw token stream, and only threshold-triggered
flushes touch flash. PR 2 industrialized the read path
(:class:`.query_engine.BatchedQueryEngine`); this engine is its write
twin, the front door every writer (TF-IDF ingest, corpus stats, the
serving prefix cache's refcount bumps) goes through instead of calling
``table_jax.update`` per raw batch:

* **host-side H_R** — a token→Δ dict accumulates (and dedups) incoming
  batches; duplicate tokens fold into one entry, Δs that cancel to zero
  drop out entirely (paper §2.6: zero-frequency entries are not
  retained in memory);
* **threshold-triggered flushes** — the device sees traffic only when
  the buffer reaches ``flush_threshold`` unique entries (or on an
  explicit :meth:`flush`/:meth:`merge`), in sorted, deterministic order;
* **fixed-shape padded chunks** — flushed entries are EMPTY-padded up
  to ``chunk``, so each table compiles exactly one update program
  regardless of stream batch sizes (no recompile per new shape);
* **donation** — dispatches go through the donated
  ``table_jax.update``/``flush`` entry points, so the table state is
  updated in place instead of copied per call;
* **automatic invalidation** — a paired
  :class:`~.query_engine.BatchedQueryEngine` is invalidated on every
  flush *by the engine*, not by each caller remembering to. Reads
  routed through :meth:`query_batch` additionally overlay the buffered
  (unflushed) Δs, so writers get read-your-writes semantics without
  forcing a premature device dispatch;
* **double-buffered async flush** (DESIGN.md §9) — with a store-owned
  dispatcher attached, :meth:`flush` *seals* H_R (the active dict swaps
  for a fresh one) and hands the sealed chunk to a background worker:
  ingest keeps filling the new active buffer while the worker drains the
  sealed one through the donated update programs. Reads overlay *both*
  buffers (active + sealed in-flight) on the device counts, so
  read-your-writes survives the flight; sealing again while a drain is
  in flight stalls until it lands (there are exactly two buffers).
  Without a dispatcher the engine drains inline, synchronously — the
  pre-PR5 discipline;
* **ledger** — :class:`WriteEngineStats` counts buffered / deduped /
  dispatched entries and flush events alongside the device-side
  ``TableStats`` wear counters, plus the async ledgers: ``overlap_us``
  (drain time hidden behind continued ingest) and ``stall_us`` (time
  ingest blocked waiting for a drain — the whole drain, when
  synchronous).

Unlike the (state-free) query engine, this engine *owns* the device
state: buffering means an ``update`` may not touch the device at all,
so the current ``DeviceTableState`` lives in ``engine.state`` and every
consumer reaches it through the engine.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np

from .spans import holding, span, traced


@dataclasses.dataclass
class WriteEngineStats:
    """Write-path counters (DESIGN.md §7), the H_R-side ledger that
    complements the device ``TableStats`` wear counters."""

    updates: int = 0             # update() calls (writer-side batches)
    entries: int = 0             # valid (token, Δ) entries received
    buffered: int = 0            # entries that opened a new H_R slot
    deduped: int = 0             # entries absorbed without opening a
                                 # slot (duplicates + cancellations);
                                 # entries == buffered + deduped
    cancelled: int = 0           # Δ sums that hit zero in H_R (§2.6)
    dispatched_entries: int = 0  # unique (token, Δ) pairs sent to device
    dispatches: int = 0          # compiled update launches (chunks)
    flushes: int = 0             # H_R drain events (explicit + auto)
    auto_flushes: int = 0        # threshold-triggered drains
    merges: int = 0              # device-merge (table flush) requests
    invalidations: int = 0       # query-engine invalidations driven
    overlap_us: int = 0          # drain time hidden behind ingest (async)
    stall_us: int = 0            # ingest time blocked on a drain: the
                                 # whole drain when synchronous, only the
                                 # double-buffer waits when async

    def as_dict(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@traced("write.dedup")
def dedup_batch(tokens, deltas, empty: int):
    """Validate and pre-fold one raw writer batch: flatten, drop ``empty``
    padding, and collapse duplicate tokens to (unique, Δ-sum) pairs.

    Returns ``(uniq, sums, n_valid)``; shared by every H_R front
    (single-table engine and the sharded store backend)."""
    flat = np.asarray(tokens).reshape(-1).astype(np.int64)
    if deltas is None:
        d = np.ones(flat.size, np.int64)
    else:
        d = np.asarray(deltas).reshape(-1).astype(np.int64)
        if d.size != flat.size:
            raise ValueError(f"deltas size {d.size} != tokens {flat.size}")
    valid = flat != empty
    n_valid = int(valid.sum())
    if n_valid == 0:
        return (np.zeros(0, np.int64),) * 2 + (0,)
    uniq, inv = np.unique(flat[valid], return_inverse=True)
    sums = np.zeros(uniq.size, np.int64)
    np.add.at(sums, inv, d[valid])
    return uniq, sums, n_valid


def fold_entry(buf: Dict[int, int], k: int, s: int) -> int:
    """Fold one (token, Δ-sum) into an H_R dict with the paper's §2.6
    semantics: duplicates accumulate, sums that hit zero drop out (never
    retained in memory). Returns +1 if a new slot opened, 0 if it folded
    into an existing slot, −1 if it cancelled (ledger: buffered /
    deduped / cancelled respectively)."""
    cur = buf.get(k)
    if cur is None:
        if s:
            buf[k] = s
            return 1
        return -1
    if cur + s:
        buf[k] = cur + s
        return 0
    del buf[k]
    return -1


class PartitionHeatLedger:
    """Per-partition write-pressure ledger shared by the wear-tracking
    backends (ISSUE 10): a staged-since-last-merge histogram plus a
    decayed per-merge heat history.

    ``note(parts_counts, wear_delta)`` is the single mutation point —
    callers hold their dispatcher lock (the single-device backend feeds
    it from ``_on_drain`` on the drain worker; the sharded backend from
    its drain body). Semantics are exactly the former
    ``DeviceBackend._on_drain`` ledgers: staged entries accumulate per
    partition; a positive ``wear_delta`` halves the existing heat and
    charges the delta to the staged partitions proportional to volume
    (recent merge pressure, not lifetime totals); ``parts_counts=None``
    marks a forced merge and clears the staged histogram after charging.

    Partition ids are caller-defined — the single-device backend uses
    change-segment partitions (MDB) or data blocks, the sharded backend
    uses *global* block ids so heat is a function of the trace, not of
    how the mesh splits it across hosts/processes.
    """

    def __init__(self) -> None:
        self.heat: Dict[int, float] = {}
        self.staged: Dict[int, int] = {}

    def note(self, parts_counts, wear_delta: float) -> None:
        if parts_counts is not None:
            for p, c in parts_counts:
                self.staged[int(p)] = self.staged.get(int(p), 0) + int(c)
        if wear_delta > 0 and self.staged:
            self.heat = {p: 0.5 * v for p, v in self.heat.items()}
            total = sum(self.staged.values())
            for p, c in self.staged.items():
                self.heat[p] = self.heat.get(p, 0.0) + wear_delta * c / total
        if parts_counts is None:
            self.staged.clear()

    def snapshot(self) -> Tuple[Dict[int, int], Dict[int, float]]:
        """Copies of (staged, heat) — take under the caller's lock, then
        combine with live-buffer pendings lock-free."""
        return dict(self.staged), dict(self.heat)

    def clear(self) -> None:
        self.heat.clear()
        self.staged.clear()


class BatchedWriteEngine:
    """H_R dedup + threshold flush + donated fixed-shape dispatch over
    ``table_jax.update``; double-buffered async drains with a dispatcher
    attached (DESIGN.md §9)."""

    # shared with the drain worker; flashlint FL006 holds every access
    # to the state lock (or an audited under-lock/quiescent method). The
    # H_R double-buffer itself lives in the store's SealedFront.
    _fl_guarded = ("state", "_staged_dirty")

    def __init__(self, cfg, state=None, chunk: int = 4096,
                 flush_threshold: Optional[int] = None,
                 query_engine=None,
                 record: Optional[List[Tuple[np.ndarray, np.ndarray]]] = None,
                 on_flush=None, dispatcher=None, wal=None):
        import jax  # deferred: sim-only users stay jax-free
        import jax.numpy as jnp

        from . import table_jax as tj
        from .store import SealedFront
        self._jax = jax
        self._jnp = jnp
        self._tj = tj
        self.cfg = cfg
        self.state = tj.init(cfg) if state is None else state
        self.chunk = int(chunk)
        self.flush_threshold = int(2 * self.chunk if flush_threshold is None
                                   else flush_threshold)
        self.query_engine = query_engine
        # optional dispatch recorder: every flushed (keys, deltas) chunk is
        # appended, letting tests/benchmarks replay the exact device
        # traffic through direct per-call updates (bit-identity oracle)
        self.record = record
        # optional wear listener: called after every device drain with
        # (drained_keys_or_None, Δtile_stores) — ``None`` keys mark the
        # forced end-of-stream merge, whose wear belongs to everything
        # staged since the last merge. Enabling it syncs the device stats
        # once per drain (flushes are rare; updates stay async).
        self.on_flush = on_flush
        # drain executor (store.FlushDispatcher or None). With one, every
        # drain runs on its worker under its lock; reads take the same
        # lock so (device state, in-flight overlay) is always a
        # consistent snapshot. Without one, drains run inline — the
        # single-threaded pre-PR5 engine needs no locking at all.
        self.dispatcher = dispatcher
        # the seal/settle/poison double-buffer lifecycle (DESIGN.md §9),
        # now owned by one SealedFront shared across backends; ``wal``
        # makes every sealed chunk durable before its drain dispatches
        self.front = SealedFront(dispatcher=dispatcher, parts=1, wal=wal)
        # device entries staged since the last merge. An adopted state may
        # arrive with a non-empty change segment, so it counts as dirty —
        # the first merge() must really run (the pre-PR5 unconditional
        # behaviour), not take the no-op path.
        self._staged_dirty = state is not None
        self.stats = WriteEngineStats()
        if dispatcher is not None:
            dispatcher.ledger = self.stats

    @property
    def _inflight(self):
        """Sealed in-flight chunk (compat alias for ``front._inflight[0]``;
        the race-harness seeded tests poke it directly)."""
        return self.front._inflight[0]

    @_inflight.setter
    def _inflight(self, value):
        self.front._inflight[0] = value

    def _lock(self, wait_span: Optional[str] = None):
        """The state lock (a null context without a dispatcher); with
        ``wait_span``, the wait to take it is timed as that span."""
        lock = (self.dispatcher.lock if self.dispatcher is not None
                else contextlib.nullcontext())
        return lock if wait_span is None else holding(lock, wait_span)

    def _submit(self, fn, label: Optional[str] = None) -> None:
        if self.dispatcher is None:
            fn()
        else:
            self.dispatcher.submit(fn, label=label)

    def _barrier(self) -> None:
        if self.dispatcher is not None:
            self.dispatcher.wait()

    def _trace(self, kind: str, resource=None, rw=None, **meta) -> None:
        """Happens-before harness event; free no-op unless a tracer is
        attached to the dispatcher (analysis.race_harness)."""
        d = self.dispatcher
        if d is not None and getattr(d, "tracer", None) is not None:
            d.tracer.record(kind, resource=resource, rw=rw, **meta)

    def _settle(self) -> None:
        """Wait out any in-flight work before sealing or taking a no-op
        decision (the double-buffer stall + poison check both live in
        :meth:`SealedFront.settle` now); a still-running job whose merge
        phase has yet to clear ``_staged_dirty`` also barriers here —
        deciding on a stale flag would schedule a redundant merge."""
        self.front.settle()

    def _tile_stores(self) -> int:  # flashlint: under-lock
        return int(np.asarray(self.state.stats.tile_stores))

    # -- the buffered write path --------------------------------------------
    def update(self, tokens, deltas=None) -> None:
        """Accumulate a (token, Δ) batch into H_R; auto-flush at the
        threshold. ``EMPTY`` tokens are padding and ignored."""
        self.stats.updates += 1
        uniq, sums, n_valid = dedup_batch(tokens, deltas, self._tj.EMPTY)
        if n_valid == 0:
            return
        self.stats.entries += n_valid
        n_new, cancelled = self.front.fold(uniq, sums)
        self.stats.cancelled += cancelled
        self.stats.buffered += n_new
        self.stats.deduped += n_valid - n_new
        if self.front.part_len() >= self.flush_threshold:
            self.stats.auto_flushes += 1
            self.flush(wait=False)

    # flashlint: quiescent (callers seal post-settle; see the docstring)
    def seal(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Swap H_R: the active buffer becomes the sealed in-flight chunk
        (read-only from here; reads keep overlaying it until its drain
        lands) and a fresh active buffer takes its place. Returns the
        sealed ``(keys, deltas)`` in sorted, deterministic dispatch
        order, or ``None`` when H_R is empty. With a WAL attached the
        sealed chunk is fsync'd before this returns.

        Callers must wait out any previous in-flight drain first — there
        are exactly two buffers (:meth:`flush` does this)."""
        out = self.front.seal()
        return None if out is None else out[0]

    # flashlint: under-lock (drain-worker body, submitted via dispatcher)
    def _dispatch(self, keys: np.ndarray, dels: np.ndarray) -> None:
        """Drain one sealed chunk to the device change segment (stage, no
        forced merge): EMPTY-padded fixed-shape chunks, donated
        dispatches; then clear the in-flight overlay and invalidate the
        paired query engine — all atomically with respect to readers
        (runs under the dispatcher lock on the drain worker, or inline
        when synchronous)."""
        jnp, tj = self._jnp, self._tj
        tj.assert_live(self.state)       # off-thread donation guard (§9)
        wear_before = self._tile_stores() if self.on_flush else 0
        step = self.chunk
        with span("drain.dispatch"):
            for lo in range(0, keys.size, step):
                pk = keys[lo:lo + step]
                pd = dels[lo:lo + step]
                pad = step - pk.size
                if pad:  # fixed shapes → one compiled program per table
                    pk = np.concatenate(
                        [pk, np.full(pad, tj.EMPTY, np.int64)])
                    pd = np.concatenate([pd, np.zeros(pad, np.int64)])
                if self.record is not None:
                    self.record.append((pk, pd))
                self.state = tj.update(self.cfg, self.state,
                                       jnp.asarray(pk, jnp.int32),
                                       jnp.asarray(pd, jnp.int32))
                self.stats.dispatches += 1
        if self.dispatcher is not None:
            # store contract (DESIGN.md §9): a completed drain means the
            # device really holds the entries — not merely that they sit
            # in XLA's async dispatch queue. The worker absorbs this
            # wait; the sync baseline pays it inline (that is the stall
            # double buffering exists to hide). Engines without a
            # dispatcher keep the bare pre-PR5 dispatch-and-go.
            with span("drain.device_wait"):
                self._jax.block_until_ready(self.state)
        self.stats.dispatched_entries += keys.size
        self._trace("state_rebind", "state", "w")
        self._staged_dirty = True
        self.front.mark_drained()
        self.stats.flushes += 1
        self._invalidate()
        if self.on_flush:
            self.on_flush(keys, self._tile_stores() - wear_before)

    # flashlint: under-lock (drain-worker body, submitted via dispatcher)
    def _merge_device(self) -> None:
        """Force the device merge of the staged change segment (runs on
        the drain worker under the dispatcher lock, or inline)."""
        tj = self._tj
        tj.assert_live(self.state)
        wear_before = self._tile_stores() if self.on_flush else 0
        with span("drain.merge"):
            self.state = tj.flush(self.cfg, self.state)
        if self.dispatcher is not None:
            with span("drain.device_wait"):           # durable, not queued
                self._jax.block_until_ready(self.state)
        self._trace("state_rebind", "state", "w")
        self.stats.merges += 1
        self._staged_dirty = False
        # conservative: the merge moves placement, not counts, but clear
        # the cache anyway — it is one invalidation per rare merge
        self._invalidate()
        if self.on_flush:
            self.on_flush(None, self._tile_stores() - wear_before)

    def flush(self, wait: bool = True):
        """Drain H_R to the device change segment (stage, no forced
        merge). With a dispatcher and ``wait=False`` the sealed buffer
        drains in the background while the caller keeps ingesting;
        ``wait=True`` is the durability barrier for the staged entries."""
        self._settle()
        sealed = self.seal()
        if sealed is not None:
            keys, dels = sealed
            self._submit(lambda: self._dispatch(keys, dels),
                         label=f"hr-drain#{self.front.seals}:{keys.size}e")
        if wait:
            self._barrier()
        # with wait=False a drain may still be rebinding the state: take
        # the lock so callers never observe a half-donated snapshot
        with self._lock():
            return self.state

    def merge(self, wait: bool = True):
        """Flush H_R, then force the device merge of any staged change
        segment (end-of-stream / checkpoint). A complete no-op — nothing
        buffered, nothing in flight, nothing staged since the last merge
        — touches neither the device nor the hot cache."""
        self._settle()
        sealed = self.seal()
        # post-settle probe: no job is in flight here, so the flag and
        # the state are stable until we submit below
        if (sealed is None
                and not self._staged_dirty):  # flashlint: disable=FL006
            if wait:
                self._barrier()
            # no-op path: crucially, no cache invalidation (a flush of
            # an empty engine must not evict every hot key)
            return self.state                 # flashlint: disable=FL006

        def job():
            if sealed is not None:
                self._dispatch(*sealed)
            self._merge_device()

        n = 0 if sealed is None else sealed[0].size
        self._submit(job, label=f"hr-merge#{self.front.seals}:{n}e")
        if wait:
            self._barrier()
        with self._lock():
            return self.state

    # finalize is the adapter-facing spelling of the same operation
    finalize = merge

    def _invalidate(self) -> None:
        if self.query_engine is not None:
            self.query_engine.invalidate()
            self.stats.invalidations += 1

    # -- read-your-writes ---------------------------------------------------
    @property
    def buffered_entries(self) -> int:
        """Unique (token, Δ) entries not yet durable on device: the
        active H_R buffer plus the sealed in-flight chunk (if a drain is
        running). Benign unlocked snapshot (monitoring only, may be
        momentarily stale); never used for control flow."""
        return self.front.entries()

    def pending(self, keys) -> np.ndarray:  # flashlint: under-lock
        """Not-yet-durable Δ per key — the overlay a consolidated read
        must add on top of the device count: the active H_R buffer plus
        the sealed in-flight chunk. Call under the dispatcher lock when
        one is attached (the drain worker clears the in-flight chunk
        under that lock, atomically with the device state rebind)."""
        return self.front.pending(np.asarray(keys).reshape(-1))

    def query_batch(self, keys) -> np.ndarray:
        """Consolidated batched read: device counts through the paired
        query engine, plus the H_R overlay (both buffers). Taken under
        the dispatcher lock, so the device lookup and the overlay always
        describe the same instant — a drain either fully landed (its
        entries are device counts, the in-flight overlay is gone) or not
        at all (they overlay) — never both, never neither."""
        if self.query_engine is None:
            raise ValueError("no paired query engine; construct with "
                             "query_engine=BatchedQueryEngine(cfg)")
        with self._lock("query.lock"):
            base = self.query_engine.query_batch(self.state, keys)
            pend = self.pending(keys)
        return base + pend

    def query(self, key: int) -> int:
        """Single-key convenience wrapper (one-element batch)."""
        return int(self.query_batch(np.asarray([key]))[0])
