"""One `FlashStore` facade over every flash-hash table backend (DESIGN.md §8).

The paper's central claim is that one deferred-update discipline — RAM
buffer H_R in front, semi-random block-local merges behind — serves every
scheme variant (§2, Fig 4). Before this module, the public surface leaked
the plumbing: every consumer manually constructed and paired a
:class:`~.write_engine.BatchedWriteEngine` with a
:class:`~.query_engine.BatchedQueryEngine`, while the sharded table
(:mod:`.distributed`) exposed a third, engine-less API with none of the
H_R dedup, donation or read-your-writes semantics. `FlashStore` is the
single entry point:

    with FlashStore.open(backend="device", scheme="MDB-L") as store:
        store.update(tokens)            # buffered in H_R
        store.increment(key, -1)        # deletion-by-decrement (§2.6)
        counts = store.query(keys)      # read-your-writes, batched
        store.flush()                   # durability point: drain + merge
        print(store.stats())

Three backends plug in behind the identical lifecycle via a small
``TableBackend`` protocol (duck-typed — ``update`` / ``query_batch`` /
``drain`` / ``flush`` / ``stats`` / ``pending_entries``):

* ``sim``     — the event-level NumPy simulator (exact SSD cost ledger;
  the paper's measurement harness). Its RAM buffer *is* H_R.
* ``device``  — the single-table JAX/Pallas path: the store owns the
  engine pair, and the flush → invalidate contract is enforced here,
  never by callers.
* ``sharded`` — the multi-device table: per-shard H_R partitions keyed
  by ``owner(x)``, shard-local flush thresholds (one hot shard drains
  its own partition without forcing every shard's buffer out), and
  cross-shard consolidated batched lookups (one psum per query chunk).

Engine pairing happens *only* in this module: constructing a write/query
engine by hand elsewhere is the pre-PR4 surface, deleted in PR 5.

Since PR 5 every backend flushes **asynchronously and double-buffered**
(DESIGN.md §9): ingest fills an active H_R buffer while a single
background worker (one :class:`FlushDispatcher` per store) drains the
sealed one through the donated update/merge programs. ``flush(wait=True)``
is the durability barrier; reads overlay both buffers plus the in-flight
chunk, so read-your-writes holds at every instant; ``async_flush=False``
restores the synchronous pre-PR5 discipline (drains still route through
the dispatcher so the ``stall_us`` ledger measures what async buys).
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from .spans import span, traced
from .table_sim import EMPTY


def _flat_i64(x) -> np.ndarray:
    return np.asarray(x).reshape(-1).astype(np.int64)


def _latest_step(path) -> Optional[int]:
    """Latest ``step_<N>`` snapshot directory under ``path`` (the
    checkpoint layout, scanned without importing jax so sim-only users
    stay jax-free)."""
    path = Path(path)
    if not path.exists():
        return None
    steps = [int(p.name.split("_")[1]) for p in path.glob("step_*")
             if p.is_dir() and not p.name.endswith(".tmp")]
    return max(steps) if steps else None


class DrainError(RuntimeError):
    """A background drain job died. Raised at the durability barrier
    (``flush(wait=True)`` / ``stats()`` / ``close()``), naming the
    failing job and chunk; the worker's original exception rides along
    as ``__cause__`` with its full traceback."""


# ---------------------------------------------------------------------------
# the drain dispatcher: one worker thread + state lock per store
# ---------------------------------------------------------------------------
class FlushDispatcher:
    """Background drain executor shared by every backend (DESIGN.md §9).

    Owns three things:

    * **the state lock** — every device-state access (drain dispatch,
      forced merge, batched lookup) runs under it, so a reader always
      sees a consistent (device state, in-flight overlay) snapshot and
      never a half-applied drain or a donated-away buffer;
    * **the one in-flight future** — double buffering means at most one
      sealed buffer is draining; submitting while it drains first waits
      it out (the stall the second buffer exists to minimise);
    * **the overlap/stall ledgers** — written into the attached
      :class:`~.write_engine.WriteEngineStats` (``ledger``): drain time
      spent on the worker counts as ``overlap_us`` (hidden behind
      ingest), caller time spent waiting counts as ``stall_us``. With
      ``enabled=False`` drains run inline and their full duration is
      ``stall_us`` — the synchronous baseline the async rows are
      measured against.

    ``wait()`` is the barrier: it re-raises any drain exception in the
    caller, so failures surface at ``flush(wait=True)`` / ``stats()`` /
    ``close()`` instead of dying silently on the worker.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self.lock = threading.RLock()
        self.ledger = None            # WriteEngineStats sink (set by owner)
        # opt-in happens-before recorder (analysis.race_harness.attach):
        # when set, submit/wait emit fork/join edges and job markers
        self.tracer = None
        self._pool = (ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="flashstore-drain")
            if self.enabled else None)
        self._future = None
        self._job_info = None         # (done-snapshot holder, job#, label)
        self._jobs = 0
        self._closed = False

    def _charge(self, field: str, t0: float) -> None:
        if self.ledger is not None:
            us = int((time.perf_counter() - t0) * 1e6)
            setattr(self.ledger, field, getattr(self.ledger, field) + us)

    def trace(self, kind: str, resource=None, rw=None, **meta) -> None:
        """Record one harness event; free no-op when no tracer attached."""
        if self.tracer is not None:
            self.tracer.record(kind, resource=resource, rw=rw, **meta)

    @property
    def pending(self) -> bool:
        """A submitted job has not been waited out yet (it may still be
        running, or be finished holding an un-raised exception)."""
        return self._future is not None

    def submit(self, fn, label: Optional[str] = None) -> None:
        """Run one sealed-buffer drain under the state lock: on the
        worker when async, inline when not. Any previous in-flight drain
        is waited out first (there are exactly two buffers). ``label``
        names the chunk in the :class:`DrainError` should the job die."""
        if self._closed:
            raise ValueError("dispatcher is closed")
        self.wait()
        job = self._jobs
        self._jobs += 1
        if not self.enabled:
            self.trace("job_start", job=job, label=label)
            t0 = time.perf_counter()
            try:
                with span("drain.job"), self.lock:
                    fn()
            finally:
                self.trace("job_end", job=job)
                self._charge("stall_us", t0)
            return

        tr = self.tracer
        snap = tr.fork() if tr is not None else None
        done = {}

        def run():
            if tr is not None:        # submit → job-start edge
                tr.join(snap)
                tr.record("job_start", job=job, label=label)
            t0 = time.perf_counter()
            try:
                with span("drain.job"), self.lock:
                    fn()
            finally:
                if tr is not None:
                    tr.record("job_end", job=job)
                    done["snap"] = tr.fork()
            self._charge("overlap_us", t0)

        self._job_info = (done, job, label)
        self._future = self._pool.submit(run)

    def wait(self) -> None:
        """Durability barrier: block until the in-flight drain (if any)
        lands. A worker exception re-raises here as a :class:`DrainError`
        naming the job and its sealed chunk, chained (``from exc``) to
        the original so the worker-side traceback survives."""
        f, self._future = self._future, None
        info, self._job_info = self._job_info, None
        if f is None:
            return
        t0 = time.perf_counter()
        try:
            with span("write.wait"):
                f.result()
        except Exception as exc:
            done, job, label = info if info else ({}, "?", None)
            chunk = f" ({label})" if label else ""
            raise DrainError(
                f"background drain job #{job}{chunk} failed: {exc}"
            ) from exc
        finally:
            self._charge("stall_us", t0)
        if self.tracer is not None and info:
            self.tracer.join(info[0].get("snap"))  # job-end → barrier edge

    def close(self) -> None:
        """Join the worker (completing any in-flight drain). Idempotent;
        re-raises a pending drain exception exactly once."""
        if self._closed:
            return
        self._closed = True
        try:
            self.wait()
        finally:
            if self._pool is not None:
                self._pool.shutdown(wait=True)


# ---------------------------------------------------------------------------
# the sealed front: one seal/settle/poison lifecycle for every backend
# ---------------------------------------------------------------------------
class SealedFront:
    """The double-buffered H_R lifecycle (DESIGN.md §9/§11), written
    once. Before ISSUE 7 each backend (`BatchedWriteEngine`,
    `SimBackend`, `ShardedBackend`) reimplemented the same machine:

    * **fold** — (token, Δ) pairs accumulate in the *active* buffer of
      their partition (one partition for single-table fronts, one per
      owner shard for the sharded store);
    * **settle** — wait out the in-flight drain; a sealed chunk still
      present *after* the barrier means its drain died (the worker
      clears delivered slots), so the front is **poisoned**: writes
      fail loudly rather than silently dropping the chunk, reads keep
      overlaying it, and ``FlashStore.restore()`` is the way back;
    * **seal** — post-settle, the active buffer swaps for a fresh one
      and becomes the read-only *in-flight* overlay; the sealed
      ``(keys, Δs)`` arrays (sorted, deterministic dispatch order) go
      to the caller for dispatch. With a WAL attached, every sealed
      part is appended and fsync'd here — **before** the drain is
      submitted — so a crash mid-drain loses nothing that was sealed;
    * **mark_drained** — worker side, under the dispatcher lock: the
      delivered parts' overlays clear (atomically with the device
      state rebind) and drain completions are logged.

    Owning the lifecycle here means the WAL hook is written once, and
    the flashlint FL006 lock discipline audits one class instead of
    three."""

    # shared with the drain worker; flashlint FL006 holds every access
    # to the state lock (or an audited under-lock/quiescent method)
    _fl_guarded = ("_inflight",)

    def __init__(self, dispatcher: Optional[FlushDispatcher] = None,
                 parts: int = 1, wal=None):
        self.dispatcher = dispatcher
        self.parts = int(parts)
        self.wal = wal
        self._buf: List[Dict[int, int]] = [dict() for _ in range(self.parts)]
        # sealed-but-draining chunks: the worker clears a part's slot
        # (under the dispatcher lock) once its entries are on device
        self._inflight: List[Optional[Dict[int, int]]] = [None] * self.parts
        self._wal_seqs: List[Optional[int]] = [None] * self.parts
        self.seals = 0

    def _trace(self, kind: str, resource=None, rw=None, **meta) -> None:
        d = self.dispatcher
        if d is not None and getattr(d, "tracer", None) is not None:
            d.tracer.record(kind, resource=resource, rw=rw, **meta)

    def _res(self, part: int) -> str:
        return ("hr:inflight" if self.parts == 1
                else f"hr:inflight[{part}]")

    # -- ingest side ---------------------------------------------------------
    @traced("write.fold")
    def fold(self, uniq: np.ndarray, sums: np.ndarray,
             owners: Optional[np.ndarray] = None) -> Tuple[int, int]:
        """Fold pre-deduped (token, Δ-sum) pairs into the active buffers
        (partitioned by ``owners`` when given). Returns
        ``(n_new_slots, n_cancelled)`` for the caller's ledger."""
        from .write_engine import fold_entry
        n_new = cancelled = 0
        if owners is None:
            buf = self._buf[0]
            for k, s in zip(uniq.tolist(), sums.tolist()):
                opened = fold_entry(buf, k, s)
                if opened > 0:
                    n_new += 1
                elif opened < 0:
                    cancelled += 1
        else:
            bufs = self._buf
            for k, s, o in zip(uniq.tolist(), sums.tolist(),
                               owners.tolist()):
                opened = fold_entry(bufs[o], k, s)
                if opened > 0:
                    n_new += 1
                elif opened < 0:
                    cancelled += 1
        self._trace("hr_write", "hr:active", "w")
        return n_new, cancelled

    def part_len(self, part: int = 0) -> int:
        """Active-buffer size of one partition (threshold decisions)."""
        return len(self._buf[part])

    def part_lens(self) -> List[int]:
        return [len(b) for b in self._buf]

    # -- lifecycle -----------------------------------------------------------
    def settle(self) -> None:
        """Barrier the in-flight drain, then fail loudly if it died.

        The pre-barrier probes are benign unlocked reads: worst case a
        redundant barrier. A sealed chunk still present *after* the
        barrier is the poison state — its drain failed (the worker
        clears delivered slots, and the barrier re-raised the worker's
        exception exactly once already): the entries are undelivered
        and the donated state is suspect."""
        d = self.dispatcher
        if (any(b is not None
                for b in self._inflight)      # flashlint: disable=FL006
                or (d is not None and d.pending)):
            if d is not None:
                d.wait()
        if any(b is not None
               for b in self._inflight):      # flashlint: disable=FL006
            raise RuntimeError(
                "store is poisoned: a drain failed and its sealed H_R "
                "chunk was never delivered — reopen from the last durable "
                "state (FlashStore.restore() clears the poison and "
                "replays the WAL)")

    # flashlint: quiescent (callers settle first; see the class docstring)
    @traced("write.seal")
    def seal(self, parts: Optional[List[int]] = None
             ) -> Optional[Dict[int, Tuple[np.ndarray, np.ndarray]]]:
        """Swap the selected partitions' active buffers into the
        in-flight overlay; returns ``{part: (sorted keys, deltas)}`` or
        ``None`` when nothing is buffered. With a WAL, every sealed
        part is logged and one fsync lands before this returns."""
        sel = [p for p in (range(self.parts) if parts is None else parts)
               if self._buf[p]]
        if not sel:
            return None
        out: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        for p in sel:
            if self._inflight[p] is not None:
                # never clobber a sealed chunk (a failed drain leaves
                # its entries here — they are still the read overlay)
                raise RuntimeError(
                    f"sealed H_R part {p} over an in-flight chunk; wait "
                    f"out the previous drain first")
            b = self._buf[p]
            keys = np.fromiter(b.keys(), np.int64, len(b))
            dels = np.fromiter(b.values(), np.int64, len(b))
            order = np.argsort(keys, kind="stable")  # deterministic
            keys, dels = keys[order], dels[order]
            out[p] = (keys, dels)
            self._inflight[p] = b
            self._buf[p] = dict()
            self._trace("swap", "hr:active", "w")
            self._trace("seal", self._res(p), "w", entries=keys.size)
            if self.wal is not None:
                self._wal_seqs[p] = self.wal.append_seal(p, keys, dels)
        self.seals += 1
        if self.wal is not None:
            self.wal.sync()           # durable before the drain dispatches
        return out

    def mark_drained(self, parts=None) -> None:  # flashlint: under-lock
        """Worker side, under the dispatcher lock: the sealed chunks are
        really on device — clear their overlays (atomically with the
        state rebind the worker just traced) and log the completions."""
        for p in (range(self.parts) if parts is None else parts):
            self._inflight[p] = None
            self._trace("inflight_clear", self._res(p), "w")
            if self.wal is not None and self._wal_seqs[p] is not None:
                self.wal.append_commit(p, self._wal_seqs[p])
                self._wal_seqs[p] = None

    # -- read-your-writes ----------------------------------------------------
    @traced("query.overlay")
    def pending(self, flat: np.ndarray,
                owners: Optional[np.ndarray] = None) -> np.ndarray:
        # flashlint: under-lock
        """Not-yet-durable Δ per key: active + in-flight buffers of each
        key's partition. Call under the dispatcher lock (the worker
        clears in-flight slots under it)."""
        self._trace("hr_read", "hr:active", "r")
        inf = self._inflight
        for p, b in enumerate(inf):
            if b:
                self._trace("hr_read", self._res(p), "r")
        if owners is None:
            buf, i0 = self._buf[0], inf[0]
            if not buf and not i0:
                return np.zeros(flat.size, np.int64)
            if i0:
                return np.fromiter(
                    (buf.get(int(k), 0) + i0.get(int(k), 0) for k in flat),
                    np.int64, flat.size)
            return np.fromiter((buf.get(int(k), 0) for k in flat),
                               np.int64, flat.size)
        if not any(self._buf) and not any(inf):
            return np.zeros(flat.size, np.int64)
        bufs = self._buf
        return np.fromiter(
            (bufs[o].get(int(k), 0)
             + (inf[o].get(int(k), 0) if inf[o] else 0)
             for k, o in zip(flat, owners)), np.int64, flat.size)

    def entries(self) -> int:
        # benign unlocked snapshot (monitoring only, may be momentarily
        # stale); never used for control flow
        return (sum(len(b) for b in self._buf)
                + sum(len(b)
                      for b in self._inflight if b))  # flashlint: disable=FL006

    @property
    def poisoned(self) -> bool:
        """An undelivered sealed chunk survives the barrier (benign
        unlocked probe: only consulted on quiesced paths)."""
        return any(b is not None
                   for b in self._inflight)           # flashlint: disable=FL006

    def clear(self) -> None:  # flashlint: quiescent (restore path, re-armed)
        """Drop every buffer — active and in-flight — clearing any
        poison. Only the restore path calls this, after re-arming the
        dispatcher: the dropped entries are exactly what the WAL replay
        re-delivers."""
        self._buf = [dict() for _ in range(self.parts)]
        self._inflight = [None] * self.parts
        self._wal_seqs = [None] * self.parts


# ---------------------------------------------------------------------------
# sim backend: the event-level SSD simulation
# ---------------------------------------------------------------------------
class SimBackend:
    """`table_sim` behind the store protocol, with the store-level
    double-buffered H_R in front (DESIGN.md §9): updates fold into an
    active host dict; sealed chunks replay into the simulator —
    ``update_batch`` is the engine-chunk-compatible ±Δ twin — on the
    drain worker, so the async lifecycle is identical across backends.
    The sim's own RAM buffer keeps playing the *costed* H_R inside the
    cost model; `query_batch` already consolidates
    data/change/overflow + buffer, and the front buffers overlay on
    top."""

    name = "sim"
    # shared with the drain worker; flashlint FL006 holds every access
    # to the state lock (or an audited under-lock/quiescent method). The
    # double-buffer itself now lives in the SealedFront.
    _fl_guarded = ("_dirty",)

    def __init__(self, geom=None, scheme: str = "MDB-L",
                 ram_buffer_pct: float = 5.0,
                 change_segment_pct: float = 12.5,
                 flush_threshold: Optional[int] = None,
                 async_flush: bool = True, wal=None, **table_kw):
        from .flash_model import TableGeometry
        from .table_sim import make_table
        from .write_engine import WriteEngineStats
        self.geom = geom if geom is not None else TableGeometry(
            num_blocks=16, pages_per_block=64, entries_per_page=64)
        self.scheme = scheme
        # ctor args kept for restore-from-scratch (no snapshot on disk)
        self._ram_pct = ram_buffer_pct
        self._cs_pct = change_segment_pct
        self._table_kw = dict(table_kw)
        self.table = make_table(scheme, self.geom, ram_buffer_pct,
                                change_segment_pct, **table_kw)
        # the front H_R seals at the costed RAM buffer's own capacity by
        # default, so threshold behaviour tracks the paper's H_R size
        self.flush_threshold = int(self.table.ram.capacity
                                   if flush_threshold is None
                                   else flush_threshold)
        self._disp = FlushDispatcher(enabled=async_flush)
        self.front = SealedFront(dispatcher=self._disp, parts=1, wal=wal)
        self._dirty = False          # sim holds undrained/unmerged entries
        self.stats_ledger = WriteEngineStats()
        self._disp.ledger = self.stats_ledger

    # -- the buffered write path -------------------------------------------
    def update(self, tokens, deltas=None) -> None:
        from .write_engine import dedup_batch
        led = self.stats_ledger
        led.updates += 1
        uniq, sums, n_valid = dedup_batch(tokens, deltas, EMPTY)
        if n_valid == 0:
            return
        led.entries += n_valid
        n_new, cancelled = self.front.fold(uniq, sums)
        led.cancelled += cancelled
        led.buffered += n_new
        led.deduped += n_valid - n_new
        if self.front.part_len() >= self.flush_threshold:
            led.auto_flushes += 1
            self.drain(wait=False)

    def _seal(self) -> Optional[tuple]:  # flashlint: quiescent (post-settle)
        out = self.front.seal()
        return None if out is None else out[0]

    def _replay(self, keys, dels, merge: bool) -> None:  # flashlint: under-lock
        # worker side, under the dispatcher lock
        led = self.stats_ledger
        if keys is not None:
            self.table.update_batch(keys, dels)
            led.dispatches += 1
            led.dispatched_entries += keys.size
            self._dirty = True
            self.front.mark_drained()
            led.flushes += 1
        if merge:
            self.table.finalize()
            led.merges += 1
            self._dirty = False
        elif keys is not None:
            self.table.flush()       # stage, no forced merge

    def drain(self, wait: bool = True) -> None:
        self.front.settle()
        sealed = self._seal()
        if sealed is not None:
            k, d = sealed
            self._disp.submit(lambda: self._replay(k, d, merge=False),
                              label=f"sim-drain#{self.front.seals}:"
                                    f"{k.size}e")
        if wait:
            self._disp.wait()

    def flush(self, wait: bool = True) -> None:  # durability point
        self.front.settle()
        sealed = self._seal()
        # post-settle probe: no job in flight, the flag is stable
        if sealed is None and not self._dirty:  # flashlint: disable=FL006
            if wait:
                self._disp.wait()
            return                    # complete no-op
        k, d = sealed if sealed is not None else (None, None)
        n = 0 if k is None else k.size
        self._disp.submit(lambda: self._replay(k, d, merge=True),
                          label=f"sim-flush#{self.front.seals}:{n}e")
        if wait:
            self._disp.wait()

    # -- read-your-writes ---------------------------------------------------
    def pending(self, keys) -> np.ndarray:  # flashlint: under-lock
        return self.front.pending(_flat_i64(keys))

    def query_batch(self, keys) -> np.ndarray:
        with self._disp.lock:
            base = np.asarray(self.table.query_batch(keys), np.int64)
            pend = self.pending(keys)
        return base + pend

    def pending_entries(self) -> int:
        return self.front.entries() + len(self.table.ram.items)

    # -- durability (DESIGN.md §11) -----------------------------------------
    # flashlint: quiescent (facade snapshots post-flush; nothing in flight)
    def snapshot_state(self, path, step: int, meta: Dict,
                       manager=None) -> Path:
        """Capture the whole costed simulator (table + its own RAM buffer
        + ledgers) with the checkpoint layout's atomic tmp+rename, as a
        pickle — the sim is a plain NumPy/host object graph, so pickling
        round-trips it exactly. ``manager`` is accepted for signature
        parity with the device backends (unused: no arrays to shard)."""
        import json
        import pickle
        path = Path(path)
        final = path / f"step_{step:08d}"
        tmp = path / f"step_{step:08d}.tmp"
        if tmp.exists():
            import shutil
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        with self._disp.lock:
            blob = pickle.dumps(self.table)
        (tmp / "sim_table.pkl").write_bytes(blob)
        (tmp / "meta.json").write_text(json.dumps(meta))
        if final.exists():
            import shutil
            shutil.rmtree(final)
        tmp.rename(final)
        return final

    # flashlint: quiescent (restore path: dispatcher re-armed, no worker)
    def restore_state(self, path, step: Optional[int] = None):
        """Load the pickled simulator from ``path`` (latest ``step_*`` or
        an explicit ``step``); with no snapshot on disk, rebuild a fresh
        table so the WAL replay starts from zero. Returns
        ``(step | None, meta)``."""
        import json
        import pickle
        from .table_sim import make_table
        if path is not None and step is None:
            step = _latest_step(path)
        if path is None or step is None:
            self.table = make_table(self.scheme, self.geom, self._ram_pct,
                                    self._cs_pct, **self._table_kw)
            self._dirty = False
            return None, {}
        d = Path(path) / f"step_{step:08d}"
        self.table = pickle.loads((d / "sim_table.pkl").read_bytes())
        self._dirty = False
        meta = json.loads((d / "meta.json").read_text())
        return step, meta

    def rearm(self) -> None:
        """Replace a (possibly wedged/poisoned) dispatcher with a fresh
        worker of the same sync/async flavour; the restore path calls
        this before clearing the front."""
        old = self._disp
        self._disp = FlushDispatcher(enabled=old.enabled)
        self._disp.ledger = self.stats_ledger
        self._disp.tracer = old.tracer
        self.front.dispatcher = self._disp
        try:
            old.close()
        except Exception:
            pass                      # the poison already surfaced once

    def partition_heat(self, keys) -> np.ndarray:
        return np.zeros(_flat_i64(keys).size)     # no device wear feed

    def wear(self) -> Dict[str, int]:
        """The sim's wear counters: ``cleans`` *is* the paper's erase
        count (the device backends' ``tile_stores`` analogue)."""
        self._disp.wait()
        led = self.table.ledger
        return {"cleans": led.cleans, "block_ops": led.block_ops,
                "page_ops": led.page_ops, "merges": led.merges,
                "stages": led.stages}

    def stats(self) -> Dict[str, int]:
        self._disp.wait()             # quiesce: one consistent ledger
        led = self.table.ledger
        q = self.table.qstats
        out = {"backend": self.name, "scheme": self.scheme,
               "cleans": led.cleans, "block_ops": led.block_ops,
               "page_ops": led.page_ops, "merges": led.merges,
               "stages": led.stages, "queries": q.queries,
               "found": q.found,
               "buffered_entries": self.pending_entries()}
        out.update({f"write_{k}": v
                    for k, v in self.stats_ledger.as_dict().items()})
        return out

    def close(self) -> None:
        self._disp.close()


# ---------------------------------------------------------------------------
# device backend: single-table engine pair
# ---------------------------------------------------------------------------
class DeviceBackend:
    """The PR-2/PR-3 engine pair, auto-wired: one
    :class:`~.write_engine.BatchedWriteEngine` owning the table state,
    one paired :class:`~.query_engine.BatchedQueryEngine`, flush →
    invalidate enforced by construction. With ``track_wear=True`` the
    backend additionally attributes per-drain ``TableStats`` wear deltas
    (Δ``tile_stores``) to change-segment partitions — the feed for
    wear-aware eviction policies (`serving/prefix_cache`)."""

    name = "device"
    # the wear ledger is mutated by _on_drain on the drain worker; FL006
    # holds every access to the state lock or an audited method
    _fl_guarded = ("_wear",)

    def __init__(self, cfg=None, state=None, chunk: int = 4096,
                 query_chunk: int = 1024,
                 flush_threshold: Optional[int] = None,
                 hot_capacity: int = 4096, track_wear: bool = False,
                 record: Optional[list] = None, async_flush: bool = True,
                 wal=None, **table_kw):
        from . import table_jax as tj
        from .query_engine import BatchedQueryEngine
        from .write_engine import BatchedWriteEngine
        self.cfg = cfg if cfg is not None else tj.FlashTableConfig(**table_kw)
        self.scheme = self.cfg.scheme
        self.query_engine = BatchedQueryEngine(
            self.cfg, chunk=query_chunk, hot_capacity=hot_capacity,
            filter_fn=((lambda state, q: tj.filter_probe(self.cfg, state, q))
                       if self.cfg.filters else None))
        self._track_wear = bool(track_wear)
        self._disp = FlushDispatcher(enabled=async_flush)
        self.writer = BatchedWriteEngine(
            self.cfg, state=state, chunk=chunk,
            flush_threshold=flush_threshold, query_engine=self.query_engine,
            record=record, on_flush=self._on_drain if track_wear else None,
            dispatcher=self._disp, wal=wal)
        # wear attribution: partition -> accumulated Δtile_stores share,
        # plus the staged-since-last-merge histogram merges are charged to
        # (the ledger is shared with the sharded backend — ISSUE 10)
        from .write_engine import PartitionHeatLedger
        self._wear = PartitionHeatLedger()

    # -- wear attribution ---------------------------------------------------
    def _partition_of(self, keys: np.ndarray) -> np.ndarray:
        """Host-side partition id: MDB's change-segment partition when the
        scheme has one, else the data block itself (finest granularity)."""
        s = self.cfg.pair.s(np.asarray(keys, np.int64))
        if self.scheme == "MDB":
            return np.asarray(s) // self.cfg.blocks_per_partition
        return np.asarray(s)

    def _on_drain(self, keys, wear_delta: int) -> None:  # flashlint: under-lock
        # the ledger charges the measured Δtile_stores to the partitions
        # staged since the last forced merge, proportional to staged
        # volume, with a decayed history (recent merge pressure, not the
        # lifetime total); keys=None marks the forced merge that drains
        # the staged histogram
        parts_counts = None
        if keys is not None:                 # H_R drain: staged entries
            parts, counts = np.unique(self._partition_of(keys),
                                      return_counts=True)
            parts_counts = list(zip(parts.tolist(), counts.tolist()))
        self._wear.note(parts_counts, wear_delta)

    def partition_heat(self, keys) -> np.ndarray:
        """Write pressure of each key's partition: entries currently
        pending for it (H_R — both buffers — + staged-unmerged; it *will*
        be rewritten at the next merge no matter what) plus the decayed
        per-merge ``TableStats`` wear history. Hot partitions are being
        rewritten anyway — re-dirtying them is nearly free; dirtying a
        cold one costs a fresh block rewrite. Takes the dispatcher lock:
        ``_on_drain`` mutates the heat ledgers on the drain worker."""
        flat = _flat_i64(keys)
        if flat.size == 0:
            return np.zeros(0)
        with self._disp.lock:
            pending, heat = self._wear.snapshot()
            for b in (self.writer.front._buf[0],
                      self.writer.front._inflight[0]):
                if not b:
                    continue
                bk = np.fromiter(b.keys(), np.int64, len(b))
                parts, counts = np.unique(self._partition_of(bk),
                                          return_counts=True)
                for p, c in zip(parts.tolist(), counts.tolist()):
                    pending[p] = pending.get(p, 0) + c
        if not pending and not heat:
            return np.zeros(flat.size)
        parts = self._partition_of(flat)
        return np.asarray([pending.get(int(p), 0)
                           + heat.get(int(p), 0.0) for p in parts])

    # -- protocol -----------------------------------------------------------
    @property
    def state(self):
        return self.writer.state

    @property
    def front(self) -> SealedFront:
        """The engine's sealed front (the store facade's lifecycle
        handle: quiesce / poison probe / WAL)."""
        return self.writer.front

    def update(self, tokens, deltas=None) -> None:
        self.writer.update(tokens, deltas)

    def query_batch(self, keys) -> np.ndarray:
        return self.writer.query_batch(keys)

    def drain(self, wait: bool = True) -> None:
        self.writer.flush(wait=wait)

    def flush(self, wait: bool = True) -> None:
        self.writer.merge(wait=wait)

    def pending_entries(self) -> int:
        return self.writer.buffered_entries

    def wear(self) -> Dict[str, int]:
        self._disp.wait()             # quiesce: device counters settled
        s = self.state.stats
        return {f: int(getattr(s, f)) for f in s._fields}

    def stats(self) -> Dict[str, int]:
        out = {"backend": self.name, "scheme": self.scheme}
        out.update(self.wear())       # barriers the in-flight drain
        out.update({f"write_{k}": v
                    for k, v in self.writer.stats.as_dict().items()})
        out.update({f"query_{k}": v
                    for k, v in self.query_engine.stats.as_dict().items()})
        out["buffered_entries"] = self.pending_entries()
        return out

    # -- durability (DESIGN.md §11) -----------------------------------------
    # flashlint: quiescent (facade snapshots post-flush; nothing in flight)
    def snapshot_state(self, path, step: int, meta: Dict,
                       manager=None) -> Path:
        """Capture the device table state through the checkpoint layout
        (atomic tmp+rename ``step_<N>/{meta.json,arrays.npz}``)."""
        from ..checkpoint.checkpoint import CheckpointManager
        if manager is None:
            # keep=huge: snapshot GC policy belongs to the caller, not
            # the durability path
            manager = CheckpointManager(path, every_steps=1, keep=1_000_000)
        manager.save(step, self.state, blocking=True, extra_meta=meta)
        return Path(path) / f"step_{step:08d}"

    # flashlint: quiescent (restore path: dispatcher re-armed, no worker)
    def restore_state(self, path, step: Optional[int] = None):
        """Load the device state from the latest (or given) snapshot
        under ``path``; with no snapshot, re-init a fresh table so the
        WAL replay starts from zero. Returns ``(step | None, meta)``."""
        import jax
        import jax.numpy as jnp

        from . import table_jax as tj
        if path is not None and step is None:
            step = _latest_step(path)
        if path is None or step is None:
            self.writer.state = tj.init(self.cfg)
            meta = {}
            step = None
        else:
            from ..checkpoint.checkpoint import restore_checkpoint
            restored, meta = restore_checkpoint(path, tj.init(self.cfg),
                                                step=step)
            # npz leaves come back as numpy; the donated update programs
            # (and assert_live) need real jax arrays
            self.writer.state = jax.tree.map(jnp.asarray, restored)
        self.writer._staged_dirty = True  # snapshot may hold staged segments
        self._wear.clear()
        self.query_engine.invalidate()
        return step, meta

    def rearm(self) -> None:
        """Replace a (possibly wedged/poisoned) dispatcher with a fresh
        worker; restore calls this before clearing the front."""
        old = self._disp
        self._disp = FlushDispatcher(enabled=old.enabled)
        self._disp.ledger = self.writer.stats
        self._disp.tracer = old.tracer
        self.writer.dispatcher = self._disp
        self.writer.front.dispatcher = self._disp
        try:
            old.close()
        except Exception:
            pass                      # the poison already surfaced once

    def close(self) -> None:
        self._disp.close()


# ---------------------------------------------------------------------------
# sharded backend: per-shard H_R partitions over the distributed table
# ---------------------------------------------------------------------------
class ShardedBackend:
    """The distributed table (:mod:`.distributed`) brought to engine
    parity — the ROADMAP "distributed sharded table at scale" item.

    * **per-shard H_R partitions** — the host buffer is split by
      ``owner(x)`` (the same two-level hash that shards the data
      segment), so dedup/cancellation state is per-shard and a drain can
      target one shard's traffic;
    * **shard-local flush thresholds** — a partition drains when *it*
      fills; the other shards' buffers stay warm (their entries keep
      absorbing duplicates) instead of being forced out by a global
      count. Because the collective is fixed-shape anyway, partitions at
      least ``piggyback_frac`` full ride along for free;
    * **owner-aligned dispatch** — drained entries are placed directly in
      their owner shard's slice of the update batch, so the ``all_to_all``
      routes every entry shard-locally (src == dst: zero cross-shard
      payload movement) and the per-(src,dst) ``bucket_cap`` can never
      overflow (``shard_chunk <= bucket_cap`` entries, all self-owned);
    * **consolidated lookups** — one shard_map'd lookup per EMPTY-padded
      query chunk serves the whole deduped batch (every shard probes its
      blocks, one psum combines), fronted by the standard
      :class:`~.query_engine.BatchedQueryEngine` hot cache + H_R overlay.

    All three schemes shard (ISSUE 10): MDB's per-change-segment-partition
    log pointers tile to a per-shard leading dim like every other leaf
    (:func:`distributed._squeeze` is scheme-aware).

    **Multi-process meshes** (ISSUE 10, DESIGN.md §14). When the process
    was brought up under ``jax.distributed.initialize`` the same backend
    runs the *cluster* edition: the mesh spans every process's devices,
    each host folds its own ingest into its host-local per-shard H_R
    partitions, and the cross-host ``all_to_all`` inside the update
    program routes drained entries to their owner's blocks. Because
    collective programs are SPMD, three rules change vs. single-host:

    * drains/flushes/queries are **collective** — every process must call
      them at the same logical point (threshold auto-flush is disabled;
      the caller drives the drain cadence);
    * hosts first **agree on the number of drain waves** (and whether a
      device merge is pending anywhere) via a tiny caller-thread
      collective run post-settle, so the worker-side collectives stay in
      global program order (``agree_k < waves_k < agree_{k+1}``) while
      still being hidden behind each host's local ingest;
    * each host packs its sealed entries into its **local device slices**
      only (``<= shard_chunk`` entries per slice, so the per-(src,dst)
      bucket can never overflow: ``write_carried == 0`` stays structural
      even though the a2a now does real cross-host routing).
    """

    name = "sharded"
    # shared with the drain worker; flashlint FL006 holds every access
    # to the state lock (or an audited under-lock/quiescent method). The
    # per-shard H_R double-buffer itself lives in the SealedFront.
    _fl_guarded = ("state", "_staged_dirty", "_wear")

    def __init__(self, cfg=None, mesh=None, axis: str = "table",
                 num_shards: Optional[int] = None,
                 shard_chunk: Optional[int] = None,
                 flush_threshold: Optional[int] = None,
                 query_chunk: int = 1024, hot_capacity: int = 4096,
                 piggyback_frac: float = 0.5, async_flush: bool = True,
                 track_wear: bool = True, wal=None, **table_kw):
        import jax
        from jax.sharding import NamedSharding

        from . import distributed as D
        from . import table_jax as tj
        from .query_engine import BatchedQueryEngine
        from .write_engine import PartitionHeatLedger, WriteEngineStats

        if cfg is None or isinstance(cfg, tj.FlashTableConfig):
            n = int(num_shards if num_shards is not None
                    else jax.device_count())
            local = cfg if cfg is not None else tj.FlashTableConfig(
                **table_kw)
            cfg = D.ShardedTableConfig(local=local, num_shards=n)
        self.cfg = cfg
        n = cfg.num_shards
        if n & (n - 1):
            raise ValueError(f"num_shards={n} must be a power of two")
        self.scheme = cfg.local.scheme
        self.mesh = mesh if mesh is not None else jax.make_mesh((n,), (axis,))
        self.axis = axis
        # multi-process mesh? (jax.distributed.initialize before open)
        self.num_processes = int(jax.process_count())
        self.process_index = int(jax.process_index())
        self.multihost = self.num_processes > 1
        # mesh positions whose device this process owns == the slices this
        # host may pack drained entries into (all of them, single-host)
        self._local_shards = (D.host_shards(self.mesh, axis)
                              if self.multihost else list(range(n)))
        self.shard_chunk = int(min(cfg.bucket_cap, shard_chunk or 1024))
        self.flush_threshold = int(2 * self.shard_chunk
                                   if flush_threshold is None
                                   else flush_threshold)
        self.piggyback_frac = float(piggyback_frac)
        self._jnp = jax.numpy
        self._upd = D.make_update_fn(cfg, self.mesh, axis,
                                     with_deltas=True, donate=True)
        self._mrg = D.make_flush_fn(cfg, self.mesh, axis, donate=True)
        self._sync = (D.make_sync_fn(cfg, self.mesh, axis)
                      if self.multihost else None)
        look = D.make_lookup_fn(cfg, self.mesh, axis, with_dist=True,
                                with_tiles=True)
        filt = (D.make_filter_fn(cfg, self.mesh, axis)
                if cfg.local.filters else None)
        if self.multihost:
            # query batches must be *global* (replicated) arrays — a
            # process-local jnp array is not addressable mesh-wide
            mesh_ = self.mesh
            lookup_fn = lambda state, q: look(
                state, D.make_replicated(mesh_, np.asarray(q)))
            filter_fn = (None if filt is None else lambda state, q: filt(
                state, D.make_replicated(mesh_, np.asarray(q))))
        else:
            lookup_fn = lambda state, q: look(state, q)
            filter_fn = (None if filt is None
                         else lambda state, q: filt(state, q))
        self.query_engine = BatchedQueryEngine(
            cfg.local, chunk=query_chunk, hot_capacity=hot_capacity,
            lookup_fn=lookup_fn, filter_fn=filter_fn)
        spec = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            D.state_pspec(axis, cfg.local),
                            is_leaf=lambda s: type(s).__name__
                            == "PartitionSpec")
        self._spec = spec             # restore reshard target
        self.state = (D.place_global(cfg, self.mesh, axis) if self.multihost
                      else jax.device_put(D.init_global(cfg), spec))
        self._shard_bits = cfg.local.q_log2 - cfg.local.r_log2
        self._staged_dirty = False    # staged entries since last merge
        self._disp = FlushDispatcher(enabled=async_flush)
        # per-shard H_R partitions behind the one sealed-front lifecycle
        self.front = SealedFront(dispatcher=self._disp, parts=n, wal=wal)
        self.stats_ledger = WriteEngineStats()
        self._disp.ledger = self.stats_ledger
        self.piggybacked = 0
        self.carried = 0
        # per-shard wear/heat (ISSUE 10): keyed by *global* block id so
        # heat is a function of the trace, not of the mesh topology; the
        # merge charge is the trace-derived staged volume (the sharded
        # TableStats deltas are not per-host-readable). track_wear is
        # accepted for DeviceBackend signature parity — the proxy feed is
        # cheap enough to keep on unconditionally.
        self._track_wear = bool(track_wear)
        self._wear = PartitionHeatLedger()

    @property
    def _inflight(self) -> List[Optional[Dict[int, int]]]:
        """Read-only view of the sealed per-shard overlays (tests probe
        it; the front owns the real slots)."""
        return self.front._inflight

    # -- owner routing ------------------------------------------------------
    def owner_of(self, keys) -> np.ndarray:
        """Owner shard per key: the global block id's top (shard) bits."""
        s = np.asarray(self.cfg.global_pair.s(_flat_i64(keys)))
        return s >> self._shard_bits

    # -- the buffered write path -------------------------------------------
    def update(self, tokens, deltas=None) -> None:
        from .write_engine import dedup_batch
        led = self.stats_ledger
        led.updates += 1
        uniq, sums, n_valid = dedup_batch(tokens, deltas, EMPTY)
        if n_valid == 0:
            return
        led.entries += n_valid
        owners = self.owner_of(uniq)
        n_new, cancelled = self.front.fold(uniq, sums, owners)
        led.cancelled += cancelled
        led.buffered += n_new
        led.deduped += n_valid - n_new
        if self.multihost:
            # drains are collective: a host-local threshold must not
            # launch a collective program the other hosts don't know
            # about. The caller drives the drain cadence (DESIGN.md §14).
            return
        lens = self.front.part_lens()
        hot = [i for i, ln in enumerate(lens)
               if ln >= self.flush_threshold]
        if hot:
            led.auto_flushes += 1
            ride = [i for i, ln in enumerate(lens)
                    if i not in hot
                    and ln >= self.piggyback_frac * self.flush_threshold]
            self.piggybacked += len(ride)
            self.drain(shards=hot + ride, wait=False)

    def _seal(self, shards=None) -> Optional[Dict]:  # flashlint: quiescent
        """Seal the selected shards' H_R partitions via the front (each
        sealed dict becomes that shard's in-flight overlay). Returns
        {shard: (sorted keys, deltas)} or None. Callers run it
        post-settle (no drain in flight)."""
        return self.front.seal(parts=shards)

    # flashlint: under-lock (drain-worker body, submitted via dispatcher)
    def _drain_sealed(self, per_shard: Dict) -> None:
        """Dispatch sealed shard partitions to their owners' change
        segments (no forced merge) — worker side, under the dispatcher
        lock. One fixed-shape collective per ``shard_chunk``-entry wave;
        every drained entry rides in its owner's slice, so the a2a is
        shard-local by construction."""
        from .distributed import assert_live
        jnp = self._jnp
        n = self.cfg.num_shards
        step = self.shard_chunk
        led = self.stats_ledger
        assert_live(self.state)       # off-thread donation guard (§9)
        waves = max(-(-ks.size // step) for ks, _ in per_shard.values())
        for w in range(waves):
            with span("drain.wave"):
                toks = np.full(n * step, EMPTY, np.int64)
                dels = np.zeros(n * step, np.int64)
                for s, (ks, vs) in per_shard.items():
                    part_k = ks[w * step:(w + 1) * step]
                    part_v = vs[w * step:(w + 1) * step]
                    toks[s * step:s * step + part_k.size] = part_k
                    dels[s * step:s * step + part_v.size] = part_v
                self.state, n_carry = self._upd(
                    self.state, jnp.asarray(toks, jnp.int32),
                    jnp.asarray(dels, jnp.int32))
                led.dispatches += 1
                # owner-aligned placement keeps every (src,dst) bucket
                # within bucket_cap, so the collective never carries over
                self.carried += int(np.asarray(n_carry).sum())
        import jax
        with span("drain.device_wait"):     # durable, not merely queued (§9)
            jax.block_until_ready(self.state)
        self._disp.trace("state_rebind", "state", "w")
        self._staged_dirty = True
        for _s, (ks, _vs) in per_shard.items():
            led.dispatched_entries += ks.size
        self._note_staged(per_shard)
        self.front.mark_drained(sorted(per_shard))
        led.flushes += 1
        self.query_engine.invalidate()
        led.invalidations += 1

    def _note_staged(self, per_shard: Dict) -> None:  # flashlint: under-lock
        """Feed the wear ledger with the drained entries, keyed by
        *global* block id — the trace-derived proxy for per-shard
        ``partition_heat`` (identical no matter how the mesh splits the
        trace across processes). Worker side, under the dispatcher lock."""
        if not self._track_wear or not per_shard:
            return
        blocks = np.concatenate(
            [np.asarray(self.cfg.global_pair.s(ks))
             for ks, _vs in per_shard.values()])
        parts, counts = np.unique(blocks, return_counts=True)
        self._wear.note(list(zip(parts.tolist(), counts.tolist())), 0)

    # flashlint: under-lock (drain-worker body, submitted via dispatcher)
    def _merge_device(self) -> None:
        """Force the device merge of all staged change segments — worker
        side, under the dispatcher lock."""
        import jax

        from .distributed import assert_live
        assert_live(self.state)
        with span("drain.merge"):
            self.state = self._mrg(self.state)
        with span("drain.device_wait"):
            jax.block_until_ready(self.state)
        self._disp.trace("state_rebind", "state", "w")
        self.stats_ledger.merges += 1
        self._staged_dirty = False
        if self._track_wear:
            # merge charge = staged volume since the last merge (the
            # trace-derived twin of DeviceBackend's Δtile_stores feed)
            self._wear.note(None, float(sum(self._wear.staged.values())))
        self.query_engine.invalidate()
        self.stats_ledger.invalidations += 1

    def _stall_if_inflight(self) -> None:
        """Wait out in-flight work before sealing or a no-op decision
        (the double-buffer stall + poison check live in
        :meth:`SealedFront.settle`); a running job whose merge phase has
        yet to settle ``_staged_dirty`` also barriers here."""
        self.front.settle()

    # -- multi-process drains (ISSUE 10, DESIGN.md §14) ----------------------
    def _agree(self, waves: int, dirty: int) -> Tuple[int, int]:
        """Caller-thread agreement collective: element-wise max over
        shards of ``(waves, dirty)``. Each process fills only its own
        shards' rows (the placement callback never asks for the others),
        so the result is the max over hosts. Runs post-settle — no worker
        collective can be in flight — keeping the global collective order
        strict: ``agree_k < waves_k < agree_{k+1}`` on every host."""
        from . import distributed as D
        v = np.zeros((self.cfg.num_shards, 2), np.int32)
        v[self._local_shards, 0] = waves
        v[self._local_shards, 1] = dirty
        got = np.asarray(self._sync(
            D.make_global_batch(self.mesh, self.axis, v)))
        return int(got[0]), int(got[1])

    def _drain_collective(self, merge: bool, wait: bool) -> None:
        """Multihost drain/flush body: seal all host-local partitions,
        agree with the other hosts on the number of fixed-shape drain
        waves (and, for a flush, whether any host still has staged
        segments), then submit ONE worker job that runs exactly the
        agreed program sequence — identical on every host (SPMD
        lockstep), with the collectives themselves hidden behind the
        next buffer's local ingest (the overlap_us ledger)."""
        per_shard = self._seal(None)
        total = (sum(ks.size for ks, _vs in per_shard.values())
                 if per_shard else 0)
        budget = len(self._local_shards) * self.shard_chunk
        waves = -(-total // budget) if total else 0
        # post-settle probe: no job in flight, the flag is stable
        dirty = 1 if (merge and
                      self._staged_dirty) else 0  # flashlint: disable=FL006
        g_waves, g_dirty = self._agree(waves, dirty)
        if g_waves == 0 and not (merge and g_dirty):
            if wait:
                self._disp.wait()
            return

        def job():
            self._drain_sealed_multihost(per_shard, g_waves)
            if merge and g_dirty:
                self._merge_device()

        kind = "flush" if merge else "drain"
        mine = sorted(per_shard) if per_shard else []
        self._disp.submit(job, label=f"mh-{kind}#{self.front.seals}:"
                                     f"waves{g_waves}:shards{mine}")
        if wait:
            self._disp.wait()

    # flashlint: under-lock (drain-worker body, submitted via dispatcher)
    def _drain_sealed_multihost(self, per_shard: Optional[Dict],
                                waves: int) -> None:
        """Run the agreed number of collective update waves, packing this
        host's sealed entries into its *local* device slices only (the
        a2a routes them to their owners across hosts). Each slice holds
        at most ``shard_chunk <= bucket_cap`` entries, so no (src, dst)
        bucket can overflow — ``write_carried == 0`` stays structural. A
        host with nothing sealed still runs its share of the waves with
        EMPTY slices (SPMD lockstep)."""
        from . import distributed as D
        from .distributed import assert_live
        n = self.cfg.num_shards
        step = self.shard_chunk
        budget = len(self._local_shards) * step
        led = self.stats_ledger
        assert_live(self.state)
        if per_shard:
            order = sorted(per_shard)
            ks = np.concatenate([per_shard[s][0] for s in order])
            vs = np.concatenate([per_shard[s][1] for s in order])
        else:
            ks = np.zeros(0, np.int64)
            vs = np.zeros(0, np.int64)
        for w in range(waves):
            with span("drain.wave"):
                toks = np.full(n * step, EMPTY, np.int64)
                dels = np.zeros(n * step, np.int64)
                ck = ks[w * budget:(w + 1) * budget]
                cv = vs[w * budget:(w + 1) * budget]
                for j, s in enumerate(self._local_shards):
                    pk = ck[j * step:(j + 1) * step]
                    pv = cv[j * step:(j + 1) * step]
                    toks[s * step:s * step + pk.size] = pk
                    dels[s * step:s * step + pv.size] = pv
                gt = D.make_global_batch(self.mesh, self.axis,
                                         toks.astype(np.int32))
                gd = D.make_global_batch(self.mesh, self.axis,
                                         dels.astype(np.int32))
                self.state, n_carry = self._upd(self.state, gt, gd)
                led.dispatches += 1
                self.carried += int(np.asarray(n_carry))
        import jax
        with span("drain.device_wait"):     # durable, not merely queued
            jax.block_until_ready(self.state)
        self._disp.trace("state_rebind", "state", "w")
        if waves:
            # other hosts' entries may have landed in our local shards'
            # change segments even when we sealed nothing
            self._staged_dirty = True
        if per_shard:
            for _s, (pks, _pvs) in per_shard.items():
                led.dispatched_entries += pks.size
            self._note_staged(per_shard)
            self.front.mark_drained(sorted(per_shard))
            led.flushes += 1
        self.query_engine.invalidate()
        led.invalidations += 1

    def drain(self, shards: Optional[List[int]] = None,
              wait: bool = True) -> None:
        """Seal the selected shards' H_R partitions and drain them on
        the worker (no forced merge). On a multi-process mesh this is a
        collective call: every process seals *all* its local partitions
        (``shards`` selection is host-local and therefore ignored) and
        the hosts agree on the wave count before the worker dispatches."""
        self._stall_if_inflight()
        if self.multihost:
            self._drain_collective(merge=False, wait=wait)
            return
        per_shard = self._seal(shards)
        if per_shard is not None:
            self._disp.submit(lambda: self._drain_sealed(per_shard),
                              label=f"shard-drain#{self.front.seals}:"
                                    f"shards{sorted(per_shard)}")
        if wait:
            self._disp.wait()

    def flush(self, wait: bool = True) -> None:
        """Durability point: drain every H_R partition, then force the
        device merge of all staged change segments. A complete no-op —
        nothing buffered, in flight or staged — touches neither the
        device nor the hot cache. Collective on a multi-process mesh
        (the merge runs on every host when *any* host has staged
        segments; the no-op decision is agreed, not local)."""
        self._stall_if_inflight()
        if self.multihost:
            self._drain_collective(merge=True, wait=wait)
            return
        per_shard = self._seal(None)
        # post-settle probe: no job is in flight here, so the flag is
        # stable until we submit below
        if (per_shard is None
                and not self._staged_dirty):  # flashlint: disable=FL006
            if wait:
                self._disp.wait()
            return

        def job():
            if per_shard is not None:
                self._drain_sealed(per_shard)
            self._merge_device()

        shards = sorted(per_shard) if per_shard else []
        self._disp.submit(job, label=f"shard-flush#{self.front.seals}:"
                                     f"shards{shards}")
        if wait:
            self._disp.wait()

    # -- read-your-writes ---------------------------------------------------
    def pending_entries(self) -> int:
        # benign unlocked snapshot (monitoring only, may be momentarily
        # stale); never used for control flow
        return self.front.entries()

    def pending(self, keys) -> np.ndarray:  # flashlint: under-lock
        """Not-yet-durable Δ per key: active + in-flight partition of the
        key's owner shard. Call under the dispatcher lock (the worker
        clears in-flight slots under it, atomically with the state
        rebind)."""
        flat = _flat_i64(keys)
        return self.front.pending(flat, self.owner_of(flat))

    def query_batch(self, keys) -> np.ndarray:
        if self.multihost:
            # lookups are collective programs: barrier the in-flight
            # drain first so every host issues them at the same point in
            # the global program order. Every process must call
            # query_batch with identical keys (DESIGN.md §14).
            self._disp.wait()
        with self._disp.lock:
            base = self.query_engine.query_batch(self.state, keys)
            pend = self.pending(keys)
        return base + pend

    def partition_heat(self, keys) -> np.ndarray:
        """Write pressure of each key's *global* block (ISSUE 10): H_R
        entries pending for it (active + in-flight, this host's view of
        the trace) plus the decayed per-merge heat history from the
        trace-derived wear proxy. Topology-invariant by construction —
        the ledger keys are global block ids, so the same trace produces
        the same heat on a 1-host-8-shard and a 2-process-4-shard mesh."""
        flat = _flat_i64(keys)
        if flat.size == 0:
            return np.zeros(0)
        with self._disp.lock:
            pending, heat = self._wear.snapshot()
            for bufs in (self.front._buf, self.front._inflight):
                for b in bufs:
                    if not b:
                        continue
                    bk = np.fromiter(b.keys(), np.int64, len(b))
                    parts, counts = np.unique(
                        np.asarray(self.cfg.global_pair.s(bk)),
                        return_counts=True)
                    for p, c in zip(parts.tolist(), counts.tolist()):
                        pending[p] = pending.get(p, 0) + c
        if not pending and not heat:
            return np.zeros(flat.size)
        parts = np.asarray(self.cfg.global_pair.s(flat))
        return np.asarray([pending.get(int(p), 0)
                           + heat.get(int(p), 0.0) for p in parts])

    def wear(self) -> Dict[str, int]:  # flashlint: quiescent
        """Device wear counters summed across shards. On a multi-process
        mesh a host can only read its addressable shards, so the counters
        are the *local* shards' sums — the per-host wear view (the drain
        routed every entry to its owner, so summing across hosts'
        reports recovers the global figure)."""
        self._disp.wait()             # quiesce: device counters settled
        s = self.state.stats

        def tot(x) -> int:
            if self.multihost:
                return int(sum(int(np.asarray(sh.data).sum())
                               for sh in x.addressable_shards))
            return int(np.asarray(x).sum())

        return {f: tot(getattr(s, f)) for f in s._fields}

    def stats(self) -> Dict[str, int]:
        out = {"backend": self.name, "scheme": self.scheme,
               "shards": self.cfg.num_shards}
        out.update(self.wear())       # barriers the in-flight drain
        out.update({f"write_{k}": v
                    for k, v in self.stats_ledger.as_dict().items()})
        out.update({f"query_{k}": v
                    for k, v in self.query_engine.stats.as_dict().items()})
        out["buffered_entries"] = self.pending_entries()
        out["write_piggybacked"] = self.piggybacked
        out["write_carried"] = self.carried
        out["buffered_per_shard_max"] = max(
            self.front.part_lens(), default=0)
        return out

    # -- durability (DESIGN.md §11) -----------------------------------------
    # flashlint: quiescent (facade snapshots post-flush; nothing in flight)
    def snapshot_state(self, path, step: int, meta: Dict,
                       manager=None) -> Path:
        """Capture the global sharded state through the checkpoint layout
        (full arrays per the single-process writer; restore reshards
        against the current mesh). Multi-process meshes recover through
        their per-host WALs instead (DESIGN.md §14): serializing a
        non-addressable global array would need a gather collective the
        checkpoint layer doesn't speak yet."""
        if self.multihost:
            raise NotImplementedError(
                "multihost sharded stores snapshot via per-host WALs "
                "(FlashStore.restore replays them); global-array "
                "snapshots need a gather the checkpoint layer lacks")
        from ..checkpoint.checkpoint import CheckpointManager
        if manager is None:
            manager = CheckpointManager(path, every_steps=1, keep=1_000_000)
        manager.save(step, self.state, blocking=True, extra_meta=meta)
        return Path(path) / f"step_{step:08d}"

    # flashlint: quiescent (restore path: dispatcher re-armed, no worker)
    def restore_state(self, path, step: Optional[int] = None):
        """Load the global state from the latest (or given) snapshot and
        device_put it against the current mesh's shardings (the elastic
        reshard); with no snapshot, re-init fresh. Returns
        ``(step | None, meta)``."""
        import jax

        from . import distributed as D
        if path is not None and step is None:
            step = _latest_step(path)
        if path is None or step is None:
            self.state = (D.place_global(self.cfg, self.mesh, self.axis)
                          if self.multihost
                          else jax.device_put(D.init_global(self.cfg),
                                              self._spec))
            meta = {}
            step = None
        else:
            if self.multihost:
                raise NotImplementedError(
                    "multihost sharded stores restore from per-host "
                    "WALs over a fresh init (path=None)")
            from ..checkpoint.checkpoint import restore_checkpoint
            restored, meta = restore_checkpoint(
                path, D.init_global(self.cfg), step=step,
                shardings=self._spec)
            self.state = restored
        self._staged_dirty = True     # snapshot may hold staged segments
        self._wear.clear()
        self.query_engine.invalidate()
        return step, meta

    def rearm(self) -> None:
        """Replace a (possibly wedged/poisoned) dispatcher with a fresh
        worker; restore calls this before clearing the front."""
        old = self._disp
        self._disp = FlushDispatcher(enabled=old.enabled)
        self._disp.ledger = self.stats_ledger
        self._disp.tracer = old.tracer
        self.front.dispatcher = self._disp
        try:
            old.close()
        except Exception:
            pass                      # the poison already surfaced once

    def close(self) -> None:
        self._disp.close()


_BACKENDS = {"sim": SimBackend, "device": DeviceBackend,
             "sharded": ShardedBackend}


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RestoreReport:
    """What :meth:`FlashStore.restore` actually did — the recovery
    audit trail (tests assert on it; operators log it)."""

    snapshot_step: Optional[int]  # step restored from (None: fresh init)
    base_seq: int                 # WAL seqs <= this were pre-rotation
    records_replayed: int         # sealed WAL chunks re-applied
    entries_replayed: int         # (token, Δ) pairs re-applied
    tail_discarded_bytes: int     # torn WAL tail dropped (warned loudly)
    poison_cleared: bool          # the store was poisoned going in
    meta: Dict                    # snapshot meta.json (includes extras)


class FlashStore:
    """Backend-agnostic counting hash table with the paper's deferred-
    update discipline built in. Construct with :meth:`open`; use as a
    context manager for automatic flush-on-exit. See the module docstring
    for the backend landscape."""

    def __init__(self, backend_impl):
        self._b = backend_impl
        self._closed = False

    @classmethod
    def open(cls, config=None, backend: str = "device", **kw) -> "FlashStore":
        """One constructor for every backend.

        ``config`` is backend-shaped — a ``TableGeometry`` for ``sim``, a
        ``FlashTableConfig`` for ``device``, a ``ShardedTableConfig`` (or
        the local ``FlashTableConfig``) for ``sharded`` — or ``None`` to
        build one from ``**kw`` (``scheme=``, ``q_log2=``, ...). Engine
        knobs (``chunk``, ``flush_threshold``, ``query_chunk``,
        ``hot_capacity``, ``async_flush``, ...) pass through as keywords;
        ``async_flush=False`` opts out of the background drain worker
        (DESIGN.md §9) for a synchronous store.

        ``wal=`` (a path, or a :class:`~.wal.WriteAheadLog`) attaches a
        chunk-granular write-ahead log: every sealed H_R chunk is
        appended and fsync'd *before* its drain dispatches, so a crash
        mid-drain loses nothing that was sealed — :meth:`restore` replays
        the log (DESIGN.md §11). Default off: the paper's numbers carry
        no WAL cost unless asked for.
        """
        try:
            impl = _BACKENDS[backend]
        except KeyError:
            raise ValueError(f"unknown backend {backend!r}; expected one "
                             f"of {tuple(_BACKENDS)}") from None
        wal = kw.pop("wal", None)
        if wal is not None and not hasattr(wal, "append_seal"):
            from .wal import WriteAheadLog
            wal = WriteAheadLog(wal)
        kw["wal"] = wal
        if config is None:
            return cls(impl(**kw))
        if backend == "sim":
            return cls(impl(geom=config, **kw))
        return cls(impl(cfg=config, **kw))

    # -- lifecycle ----------------------------------------------------------
    def _check_open(self) -> None:
        if self._closed:
            raise ValueError("store is closed")

    def close(self) -> None:
        """Flush (durability point) and release the store: any in-flight
        drain completes, the buffers empty, the drain worker joins.
        Idempotent — a second close (or ``__exit__`` after an explicit
        close) does nothing. If the final flush fails (e.g. the store
        was poisoned by an earlier drain failure), the error propagates
        but the worker is still joined and the store still ends closed —
        no thread leak, no close() loop."""
        if self._closed:
            return
        try:
            self._b.flush(wait=True)
        finally:
            self._b.close()
            if self._b.front.wal is not None:
                self._b.front.wal.close()
            self._closed = True

    def __enter__(self) -> "FlashStore":
        self._check_open()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # an exception mid-stream still drains H_R: buffered counts are
        # the caller's data, not scratch
        self.close()

    # -- writes -------------------------------------------------------------
    @traced("update")
    def update(self, tokens, deltas=None) -> None:
        """Accumulate a (token[, Δ]) batch into H_R. Duplicates fold,
        zero-sum Δs cancel (§2.6), EMPTY tokens are padding; the device
        sees traffic only at flush thresholds."""
        self._check_open()
        self._b.update(tokens, deltas)

    def increment(self, key: int, delta: int = 1) -> None:
        """Single-key counter bump; ``delta=-1`` is the paper's
        deletion-by-decrement."""
        self.update(np.asarray([key], np.int64),
                    np.asarray([delta], np.int64))

    @traced("flush")
    def flush(self, wait: bool = True) -> None:
        """Durability point: drain H_R and force the device merge of any
        staged change segment (end-of-stream / checkpoint).

        ``wait=True`` (default) is the durability **barrier**: when it
        returns, every buffered entry is on device and any drain error
        has been re-raised here. ``wait=False`` schedules the drain+merge
        on the background worker and returns immediately — ingest can
        continue; a later ``flush()``/``stats()``/``close()`` barriers.
        A flush with nothing buffered, in flight or staged is a complete
        no-op (in particular, it does not invalidate the hot-key cache)."""
        self._check_open()
        self._b.flush(wait=wait)

    @traced("drain")
    def drain(self, wait: bool = True) -> None:
        """Stage H_R to the device change segment without forcing the
        merge (the cheap half of :meth:`flush`): sealed entries reach
        flash as sequential change-segment writes, data blocks are not
        rewritten. Same ``wait`` semantics as :meth:`flush`."""
        self._check_open()
        self._b.drain(wait=wait)

    # -- reads --------------------------------------------------------------
    @traced("query")
    def query(self, keys):
        """Counts for ``keys`` — scalar in, ``int`` out; array-like in,
        ``int64`` array out (aligned with the flattened input). Reads are
        read-your-writes: buffered H_R deltas overlay device counts."""
        self._check_open()
        if np.isscalar(keys) or (isinstance(keys, np.ndarray)
                                 and keys.ndim == 0):
            return int(self._b.query_batch(np.asarray([keys]))[0])
        return self._b.query_batch(keys)

    @traced("query")
    def query_batch(self, keys) -> np.ndarray:
        """Alias of :meth:`query` for unambiguously-batched call sites."""
        self._check_open()
        return self._b.query_batch(keys)

    # -- introspection ------------------------------------------------------
    @property
    def backend(self) -> str:
        return self._b.name

    @property
    def scheme(self) -> str:
        return self._b.scheme

    @property
    def cfg(self):
        return getattr(self._b, "cfg", None)

    @property
    def state(self):
        """Device table state (device/sharded backends)."""
        return getattr(self._b, "state", None)

    @property
    def buffered_entries(self) -> int:
        return self._b.pending_entries()

    def stats(self) -> Dict[str, int]:
        """One flat ledger: device wear (``tile_stores`` = paper cleans)
        or sim I/O counters, plus ``write_*`` (H_R, including the async
        ``write_overlap_us``/``write_stall_us`` flush ledgers) and
        ``query_*`` (batched read path) counters. Barriers any in-flight
        drain first, so the ledger is a consistent snapshot."""
        return self._b.stats()

    def wear(self) -> Dict[str, int]:
        """The backend's wear counters: device/sharded ``TableStats``
        fields (``tile_stores`` = paper cleans), sim ledger counters
        (``cleans`` itself)."""
        return self._b.wear()

    def partition_heat(self, keys) -> np.ndarray:
        """Per-key wear heat of the key's change-segment partition (device
        backend with ``track_wear=True``; zeros elsewhere). Feed for
        wear-aware eviction: re-dirtying a hot partition is nearly free."""
        return self._b.partition_heat(keys)

    # -- durability: snapshot / restore (DESIGN.md §11) ----------------------
    @property
    def wal(self):
        """The attached :class:`~.wal.WriteAheadLog` (None without one)."""
        return self._b.front.wal

    def quiesce(self) -> None:
        """Join any in-flight drain without forcing new device traffic —
        the barrier ``CheckpointManager`` takes before serializing, so a
        checkpoint never captures a mid-donation state. Raises if the
        store is poisoned (the snapshot would be missing a sealed
        chunk)."""
        self._check_open()
        self._b.front.settle()

    def snapshot(self, path, step: Optional[int] = None,
                 extra_meta: Optional[Dict] = None, manager=None) -> Path:
        """Durability capture: flush everything (drain + device merge,
        the barrier), write the device/sim state through the checkpoint
        layout under ``path``, then **rotate** the WAL — every logged
        chunk is now redundant with the snapshot. Returns the snapshot
        directory.

        ``step`` defaults to one past the latest snapshot under ``path``
        (0 for the first). ``extra_meta`` rides along in ``meta.json``
        (e.g. ``CorpusStats`` counters)."""
        self._check_open()
        self._b.flush(wait=True)
        wal = self._b.front.wal
        base = wal.last_seq if wal is not None else 0
        if step is None:
            latest = _latest_step(path)
            step = 0 if latest is None else latest + 1
        meta = {"wal_base_seq": base, "store_backend": self.backend,
                "store_scheme": self.scheme}
        meta.update(extra_meta or {})
        out = self._b.snapshot_state(path, step, meta, manager=manager)
        if wal is not None:
            wal.rotate()
        return out

    def restore(self, path=None, step: Optional[int] = None
                ) -> RestoreReport:
        """Recover to the last durable state: drop every buffer (clearing
        any poison), re-arm the drain worker, load the latest snapshot
        under ``path`` (fresh-init when ``path`` is None or holds no
        snapshot), then replay sealed-but-uncovered WAL records — seqs
        after the snapshot's ``wal_base_seq`` — through the normal update
        path (appends suppressed, so restoring twice is idempotent).

        The recovery contract (DESIGN.md §11): after ``restore()``, the
        store holds exactly the deltas that were sealed before the crash
        — no lost chunks (seal fsyncs before dispatch), no double-applied
        chunks (the snapshot rotates the log; replay reapplies onto the
        snapshot, or onto a fresh table covering seq 0). Entries that
        were still in the *active* buffer (never sealed) are the one
        permissible loss — exactly the paper's H_R volatility window."""
        b = self._b
        try:
            b._disp.wait()            # settle what can settle; poison is
        except Exception:
            pass                      # cleared below, not re-raised here
        poisoned = b.front.poisoned
        b.rearm()
        b.front.clear()
        self._closed = False          # restore reopens a closed store
        snap_step, snap_meta = b.restore_state(path, step)
        base = int(snap_meta.get("wal_base_seq", 0))
        records_replayed = entries_replayed = 0
        discarded = 0
        wal = b.front.wal
        if wal is not None:
            from .wal import SEAL, WriteAheadLog, read_wal
            if wal._f.closed:         # restoring a closed store: reopen
                wal = WriteAheadLog(wal.path, fsync=wal._do_fsync)
                b.front.wal = wal
            records, discarded = read_wal(wal.path)
            seals = sorted((r for r in records
                            if r.kind == SEAL and r.seq > base),
                           key=lambda r: r.seq)
            with wal.suppressed():
                for r in seals:
                    b.update(r.keys, r.deltas)
                    records_replayed += 1
                    entries_replayed += int(r.keys.size)
                # multihost: drain() is collective — every host must call
                # it even with zero seal records of its own (per-host WALs
                # recover independently but drain in lockstep, §14)
                if seals or getattr(b, "multihost", False):
                    b.drain(wait=True)
        return RestoreReport(
            snapshot_step=snap_step, base_seq=base,
            records_replayed=records_replayed,
            entries_replayed=entries_replayed,
            tail_discarded_bytes=discarded, poison_cleared=poisoned,
            meta=snap_meta)


__all__ = ["FlashStore", "FlushDispatcher", "DrainError", "SealedFront",
           "RestoreReport", "SimBackend", "DeviceBackend", "ShardedBackend",
           "EMPTY"]
