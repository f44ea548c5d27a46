"""Segment layer of the device flash-hash table (DESIGN.md §3, §7).

The paper's table is a composition of four regions — the *data segment*
(closed hash table in blocks), the *change segment* (either a monolithic
log or ``cs_partitions`` partitioned buffers), the *overflow region*, and
the RAM buffer H_R.  This module owns the on-device state record for the
first three and every op that is shared between the MB / MDB / MDB-L
policies; :mod:`table_jax` is reduced to scheme policy (when to stage,
when to drain) over these primitives, and :mod:`write_engine` is the
host-side H_R in front of them.

Shared primitives
-----------------
* :func:`scatter_rows`   — pointer-bumped append into per-row buffers:
  the MDB partitioned change segment (``cs_partitions`` rows).
* :func:`append_overflow` / :func:`append_log` /
  :func:`scatter_partitions` — the three staging surfaces.
  ``append_overflow`` compacts a merge's front-packed spill by per-row
  counts, so its cost follows the spill's rows and the region's
  capacity, not the spill array's slots.
* :func:`merge_dirty_batch` / :func:`drain_log` /
  :func:`merge_partition` — the merge paths (all through the
  ``merge_dirty`` Pallas kernel; wear accounted per dirty block).
* :func:`scan_segment`    — batched masked scan used by the query path.
* :func:`accumulate_deltas` — sort+segment-sum dedup of a (token, Δ)
  batch (the in-kernel RAM-buffer analogue).

Functions take the table config duck-typed (anything with ``pair``,
``num_blocks``, ``max_updates_per_block`` and — for the
partitioned ops — ``cs_partitions`` / ``blocks_per_partition`` /
``partition_capacity``), so this module has no import cycle with
:mod:`table_jax`.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels.flash_hash import ops as hops
from .hashing import bloom_positions

EMPTY = hops.EMPTY


class TableStats(NamedTuple):
    tile_loads: jax.Array       # blocks read from HBM during merges
    tile_stores: jax.Array      # blocks rewritten (the paper's "cleans")
    staged_entries: jax.Array   # entries appended to the log (seq writes)
    merges: jax.Array
    stages: jax.Array
    dropped: jax.Array          # capacity losses (should be 0)
    carried: jax.Array          # updates deferred past a tile's max_u cap


class DeviceTableState(NamedTuple):
    keys: jax.Array        # (n_b, 1, r) int32 — data segment, one row
                           # per block in the kernels' tile layout
    counts: jax.Array      # (n_b, 1, r) int32
    log_keys: jax.Array    # change segment: (log_cap,) for MDB-L,
                           # (cs_partitions, part_cap) for MDB
    log_counts: jax.Array  # same shape as log_keys
    log_ptr: jax.Array     # () int32 for MDB-L, (cs_partitions,) for MDB
    ov_keys: jax.Array     # (ov_cap,) int32 — overflow region
    ov_counts: jax.Array   # (ov_cap,) int32
    ov_ptr: jax.Array      # () int32
    filter_words: jax.Array  # (n_b, 1, fw) uint32 — per-block blocked-Bloom
                             # filter rows (DESIGN.md §12). Monotone: bits
                             # are only ever OR'd in, covering every key in
                             # the data/change/overflow segments, so a
                             # filter-negative is a definitive miss.
    stats: TableStats


def zero_stats() -> TableStats:
    z = lambda: jnp.zeros((), jnp.int32)
    return TableStats(tile_loads=z(), tile_stores=z(), staged_entries=z(),
                      merges=z(), stages=z(), dropped=z(), carried=z())


def init_state(num_blocks: int, block_entries: int, log_shape,
               log_ptr_shape, overflow_capacity: int,
               filter_words: int) -> DeviceTableState:
    """Fresh segment state: EMPTY data/change/overflow regions."""
    return DeviceTableState(
        keys=jnp.full((num_blocks, 1, block_entries), EMPTY, jnp.int32),
        counts=jnp.zeros((num_blocks, 1, block_entries), jnp.int32),
        log_keys=jnp.full(log_shape, EMPTY, jnp.int32),
        log_counts=jnp.zeros(log_shape, jnp.int32),
        log_ptr=jnp.zeros(log_ptr_shape, jnp.int32),
        ov_keys=jnp.full((overflow_capacity,), EMPTY, jnp.int32),
        ov_counts=jnp.zeros((overflow_capacity,), jnp.int32),
        ov_ptr=jnp.zeros((), jnp.int32),
        filter_words=jnp.zeros((num_blocks, 1, filter_words), jnp.uint32),
        stats=zero_stats(),
    )


# ---------------------------------------------------------------------------
# per-block blocked-Bloom filter (DESIGN.md §12)
# ---------------------------------------------------------------------------
@jax.named_scope("filter_or")
def filter_or_keys(pair, filt, keys):
    """OR the Bloom bits of ``keys`` into their home blocks' filter rows.

    Maintenance is *monotone*: the device table never removes keys
    (counting semantics — deletion is a −Δ on the count), so filter bits
    are only ever set. Every staging and merge path can therefore OR its
    keys in independently, in any order, without coordination, and the
    no-false-negative invariant holds by induction over key entry points
    (DESIGN.md §12). ``EMPTY`` keys are padding and contribute nothing.

    JAX has no ``.at[].or_``, so the scatter-OR is: flatten each
    (key, probe) to a global bit id, sort, drop duplicate heads, then
    ``.at[].add`` the single-bit masks — after dedup all bits are
    distinct, so add ≡ or.
    """
    n_b, _, fw = filt.shape
    bits_log2 = (fw * 32).bit_length() - 1
    valid = keys != EMPTY
    blk = jnp.where(valid, pair.s(keys), n_b).astype(jnp.int32)
    base = blk * (fw * 32)
    fids = jnp.concatenate(
        [base + p.astype(jnp.int32) for p in bloom_positions(keys, bits_log2)])
    fids = jnp.sort(fids)
    is_head = jnp.concatenate([jnp.ones((1,), bool), fids[1:] != fids[:-1]])
    is_head &= fids < n_b * fw * 32
    word = jnp.where(is_head, fids >> 5, n_b * fw)
    mask = jnp.where(
        is_head,
        jnp.left_shift(jnp.int32(1), fids & 31).astype(jnp.uint32),
        jnp.uint32(0))
    new = jnp.zeros_like(filt).at[word // fw, 0, word % fw].add(
        mask, mode="drop")
    return filt | new


def filter_may_contain(pair, filt, q):
    """Test a query batch against the per-block filters (plain XLA).

    Returns a bool ``(Q,)`` mask: False ⇒ the key is definitively absent
    from the data, change and overflow segments (the filter covers all
    three); True ⇒ maybe present (~5% false positives at design load).
    ``EMPTY`` keys test False. This is the engine-level pre-filter; the
    in-kernel twin is :func:`kernel.filter_probe_grid`.
    """
    fw = filt.shape[-1]
    bits_log2 = (fw * 32).bit_length() - 1
    valid = q != EMPTY
    blk = jnp.where(valid, pair.s(q), 0).astype(jnp.int32)
    may = valid
    for p in bloom_positions(q, bits_log2):
        word = filt[blk, 0, (p >> jnp.uint32(5)).astype(jnp.int32)]
        may &= ((word >> (p & jnp.uint32(31))) & jnp.uint32(1)) != 0
    return may


@jax.named_scope("rebuild_filters")
def rebuild_filters(pair, state: DeviceTableState) -> DeviceTableState:
    """Recompute every filter row from the live segments.

    Normal operation never needs this (maintenance is incremental and
    monotone); it exists for filter-width migrations and as the oracle
    the property tests compare incremental maintenance against. The
    result is a *superset* of the minimal bit set only through overflow
    keys whose home tile later compacted — same conservative direction
    as incremental maintenance."""
    filt = jnp.zeros_like(state.filter_words)
    for keys in (state.keys.reshape(-1), state.log_keys.reshape(-1),
                 state.ov_keys):
        filt = filter_or_keys(pair, filt, keys)
    return state._replace(filter_words=filt)


@jax.jit
def accumulate_deltas(tokens, deltas):
    """RAM-buffer dedup with explicit deltas (supports deletion-by-−1)."""
    order = jnp.argsort(tokens, stable=True)
    t = tokens[order]
    d = deltas[order]
    is_head = jnp.concatenate([jnp.ones((1,), bool), t[1:] != t[:-1]])
    is_head &= t != EMPTY
    seg = jnp.cumsum(is_head) - 1
    sums = jax.ops.segment_sum(jnp.where(t != EMPTY, d, 0), seg,
                               num_segments=t.shape[0])
    comp = jnp.argsort(jnp.where(is_head, 0, 1), stable=True)
    keys = jnp.where(is_head[comp], t[comp], EMPTY)
    cnts = jnp.where(is_head[comp],
                     sums[jnp.clip(seg[comp], 0, t.shape[0] - 1)], 0)
    return keys, cnts.astype(jnp.int32)


def assert_live(state) -> None:
    """Off-thread donation guard (DESIGN.md §9).

    ``update``/``flush`` donate the state, and since the store's flush
    went asynchronous those donations happen on a background worker: a
    dispatch that starts from an already-donated value would die deep in
    XLA with an opaque deleted-buffer error. Every drain calls this on
    the state it is about to donate — a failure means two drains raced,
    or a caller reused a stale reference it captured before a drain."""
    for leaf in jax.tree.leaves(state):
        if getattr(leaf, "is_deleted", None) is not None and leaf.is_deleted():
            raise RuntimeError(
                "device table state was already donated: a drain is "
                "running (or ran) on this value — rebind state after "
                "every update/flush and never dispatch two drains on "
                "the same state (DESIGN.md §9)")


def compact(keys, counts):
    """Compact valid entries to the front, EMPTY-pad the tail."""
    valid = keys != EMPTY
    comp = jnp.argsort(~valid, stable=True)
    return (jnp.where(valid[comp], keys[comp], EMPTY),
            jnp.where(valid[comp], counts[comp], 0),
            valid.sum(dtype=jnp.int32))


# ---------------------------------------------------------------------------
# pointer-bumped staging (overflow region + partitioned change segment)
# ---------------------------------------------------------------------------
@jax.named_scope("scatter_rows")
def scatter_rows(buf_keys, buf_counts, ptrs, rows, keys, cnts):
    """Pointer-bumped append of (keys, cnts) into per-row buffers.

    ``buf_keys``/``buf_counts`` are ``(R, cap)``; ``ptrs`` is the ``(R,)``
    per-row fill pointer; ``rows`` assigns each entry a destination row
    (``EMPTY`` keys or rows outside ``[0, R)`` are padding and ignored).
    Entries are packed at their row's pointer in stable input order — the
    paper's semi-random page-write discipline. Entries past a row's
    capacity do *not* fit and are returned for the caller to handle
    (retry after a drain).

    Returns ``(buf_keys, buf_counts, new_ptrs, rest_keys, rest_cnts,
    n_fit)``: rest_* hold the non-fitting entries (EMPTY-masked, same
    ``(U,)`` layout), ``n_fit`` the per-row appended count.
    """
    R, cap = buf_keys.shape
    (U,) = keys.shape
    valid = (keys != EMPTY) & (rows >= 0) & (rows < R)
    rw = jnp.where(valid, rows, R).astype(jnp.int32)
    order = jnp.argsort(rw, stable=True)
    sk, sc, sr = keys[order], cnts[order], rw[order]
    start = jnp.searchsorted(sr, jnp.arange(R + 1, dtype=sr.dtype))
    rank = jnp.arange(U, dtype=jnp.int32) - start[jnp.clip(sr, 0, R)]
    pos = ptrs[jnp.clip(sr, 0, R - 1)] + rank
    fits = (sr < R) & (pos < cap)
    row = jnp.where(fits, sr, R)
    col = jnp.where(fits, pos, 0)
    buf_keys = buf_keys.at[row, col].set(sk, mode="drop")
    buf_counts = buf_counts.at[row, col].set(sc, mode="drop")
    n_fit = jnp.zeros((R,), jnp.int32).at[row].add(fits.astype(jnp.int32),
                                                   mode="drop")
    rest = (sr < R) & ~fits
    rest_k = jnp.where(rest, sk, EMPTY)
    rest_c = jnp.where(rest, sc, 0)
    return buf_keys, buf_counts, ptrs + n_fit, rest_k, rest_c, n_fit


@jax.named_scope("append_overflow")
def append_overflow(state: DeviceTableState, spill_k, spill_c
                    ) -> DeviceTableState:
    """Compact a merge's spill into the overflow region (page-chained in
    the paper; a pointer-bumped array here). Entries past the capacity
    are genuine losses, surfaced in ``stats.dropped``.

    ``spill_k``/``spill_c`` are the merge kernel's ``(n_rows, 1, max_u)``
    spill, each row front-packed (its spills in columns ``[0, n_i)``,
    EMPTY after). The spill is appended in row-major order: each overflow
    slot finds its source row by a binary search of the rows' running
    spill counts, so the cost is ``n_rows`` counts plus one gather per
    slot, however few entries the merge spills.
    """
    n_rows = spill_k.shape[0]
    sk = spill_k.reshape(n_rows, -1)
    sc = spill_c.reshape(n_rows, -1)
    cap = state.ov_keys.shape[0]
    per_row = (sk != EMPTY).sum(axis=1, dtype=jnp.int32)
    ends = jnp.cumsum(per_row, dtype=jnp.int32)
    total = ends[-1]
    n_fit = jnp.minimum(jnp.maximum(cap - state.ov_ptr, 0), total)
    j = jnp.arange(cap, dtype=jnp.int32) - state.ov_ptr  # spill offset
    take = (j >= 0) & (j < n_fit)
    row = jnp.minimum(jnp.searchsorted(ends, j, side="right"), n_rows - 1)
    col = jnp.clip(j - ends[row] + per_row[row], 0, sk.shape[1] - 1)
    return state._replace(
        ov_keys=jnp.where(take, sk[row, col], state.ov_keys),
        ov_counts=jnp.where(take, sc[row, col], state.ov_counts),
        ov_ptr=state.ov_ptr + n_fit,
        stats=state.stats._replace(
            dropped=state.stats.dropped + total - n_fit))


@jax.named_scope("append_log")
def append_log(cfg, state: DeviceTableState, keys, cnts) -> DeviceTableState:
    """Append a deduped chunk to the monolithic log (sequential write).

    Pure staging primitive: the caller (:func:`table_jax._stage`)
    guarantees the chunk fits behind ``log_ptr`` (merging first if not).
    """
    log_keys = jax.lax.dynamic_update_slice(state.log_keys, keys,
                                            (state.log_ptr,))
    log_counts = jax.lax.dynamic_update_slice(state.log_counts, cnts,
                                              (state.log_ptr,))
    n_new = (keys != EMPTY).sum(dtype=jnp.int32)
    stats = state.stats._replace(
        staged_entries=state.stats.staged_entries + n_new,
        stages=state.stats.stages + 1)
    # staged keys become device-visible here, so their filter bits must be
    # set *now* — a filter-negative must also rule out the change segment
    return state._replace(log_keys=log_keys, log_counts=log_counts,
                          log_ptr=state.log_ptr + keys.shape[0],
                          filter_words=filter_or_keys(
                              cfg.pair, state.filter_words, keys),
                          stats=stats)


def partition_of(cfg, keys):
    """MDB: partition id per key; invalid keys map to the sentinel P."""
    P = cfg.cs_partitions
    return jnp.where(keys != EMPTY,
                     cfg.pair.s(keys) // cfg.blocks_per_partition,
                     P).astype(jnp.int32)


def scatter_partitions(cfg, state: DeviceTableState, keys, cnts):
    """Append a deduped chunk into its partitions (semi-random page
    writes). Returns (state, rest_keys, rest_counts): entries whose
    partition was full are *not* staged and come back EMPTY-masked for
    the caller to retry after a merge."""
    log_keys, log_counts, log_ptr, rest_k, rest_c, n_fit = scatter_rows(
        state.log_keys, state.log_counts, state.log_ptr,
        partition_of(cfg, keys), keys, cnts)
    stats = state.stats._replace(
        staged_entries=state.stats.staged_entries
        + n_fit.sum(dtype=jnp.int32))
    # conservative filter maintenance: OR in *all* valid keys, including
    # the non-fitting rest — those retry (and land) right after the
    # partition merge, so pre-setting their bits is a harmless superset
    state = state._replace(log_keys=log_keys, log_counts=log_counts,
                           log_ptr=log_ptr,
                           filter_words=filter_or_keys(
                               cfg.pair, state.filter_words, keys),
                           stats=stats)
    return state, rest_k, rest_c


# ---------------------------------------------------------------------------
# merge paths (all through the merge_dirty Pallas kernel)
# ---------------------------------------------------------------------------
def merge_dirty_batch(cfg, state: DeviceTableState, keys, cnts):
    """One dirty-block merge pass over a flat batch of staged updates.

    The dirty set is computed from the staged keys' ``s()`` values; the
    kernel grid walks a *permutation* of all blocks with the dirty ones
    first (every block id appears exactly once, so revisit hazards cannot
    arise), but only the dirty prefix carries updates and only it is
    charged to ``tile_loads``/``tile_stores``. Updates beyond a block's
    ``max_updates_per_block`` are returned as carry and must stay staged.

    Pallas grids are static, so the permutation still has ``num_blocks``
    steps — the clean suffix is a no-op visit, and the *counters* (not
    the kernel walltime) model the paper's per-scheme cleans here. A
    truly partial grid needs a statically-known dirty count; that is
    exactly what MDB's partition layout provides
    (:func:`merge_partition`, grid length ``k``).
    """
    pair = cfg.pair
    n_b = cfg.num_blocks
    with jax.named_scope("dirty_perm"):
        valid = keys != EMPTY
        blk = jnp.where(valid, pair.s(keys), 0).astype(jnp.int32)
        per_block = jnp.zeros((n_b,), jnp.int32).at[blk].add(
            valid.astype(jnp.int32))
        dirty = per_block > 0
        # grid order: dirty blocks (ascending id — the semi-random write
        # discipline), then clean blocks with EMPTY update rows (no-op visits).
        perm = jnp.argsort(jnp.where(dirty, 0, 1),
                           stable=True).astype(jnp.int32)
        inv = jnp.zeros((n_b,), jnp.int32).at[perm].set(
            jnp.arange(n_b, dtype=jnp.int32))
        rows = jnp.where(valid, inv[blk], n_b).astype(jnp.int32)
    uk, uc, carry_k, carry_c, n_carried = hops.bucket_rows(
        rows, keys, cnts, n_b, cfg.max_updates_per_block)
    with jax.named_scope("merge_dirty"):
        nk, nc, nf, spill_k, spill_c = hops.merge_dirty(
            pair, state.keys, state.counts, state.filter_words, perm, uk, uc)
    state = state._replace(keys=nk, counts=nc, filter_words=nf)
    state = append_overflow(state, spill_k, spill_c)
    n_dirty = dirty.sum(dtype=jnp.int32)
    stats = state.stats._replace(
        tile_loads=state.stats.tile_loads + n_dirty,
        tile_stores=state.stats.tile_stores + n_dirty,
        carried=state.stats.carried + n_carried)
    return state._replace(stats=stats), carry_k, carry_c


@jax.named_scope("drain_log")
def drain_log(cfg, state: DeviceTableState) -> DeviceTableState:
    """Drain the monolithic log into the data segment (dirty-block merge).

    Carried updates (exceeded a tile's max_u) stay staged, compacted to
    the log head; everything else is cleared."""
    state, carry_k, carry_c = merge_dirty_batch(
        cfg, state, state.log_keys, state.log_counts)
    log_keys, log_counts, n_carry = compact(carry_k, carry_c)
    stats = state.stats._replace(merges=state.stats.merges + 1)
    return state._replace(log_keys=log_keys, log_counts=log_counts,
                          log_ptr=n_carry, stats=stats)


@jax.named_scope("merge_partition")
def merge_partition(cfg, state: DeviceTableState, p) -> DeviceTableState:
    """Drain change-segment partition ``p`` into its ``k`` data blocks.

    The dirty set is exactly the partition's block range
    ``[p*k, (p+1)*k)`` — the paper's §2.4 CS-block merge — so the merge
    costs ``k`` tile loads + stores, never ``num_blocks``."""
    pair = cfg.pair
    k = cfg.blocks_per_partition
    sk = jax.lax.dynamic_index_in_dim(state.log_keys, p, keepdims=False)
    sc = jax.lax.dynamic_index_in_dim(state.log_counts, p, keepdims=False)
    rows = jnp.where(sk != EMPTY, pair.s(sk) - p * k, k).astype(jnp.int32)
    uk, uc, carry_k, carry_c, n_carried = hops.bucket_rows(
        rows, sk, sc, k, cfg.max_updates_per_block)
    dirty = (p * k + jnp.arange(k)).astype(jnp.int32)
    with jax.named_scope("merge_dirty"):
        nk, nc, nf, spill_k, spill_c = hops.merge_dirty(
            pair, state.keys, state.counts, state.filter_words, dirty, uk, uc)
    state = state._replace(keys=nk, counts=nc, filter_words=nf)
    state = append_overflow(state, spill_k, spill_c)
    # carried updates stay staged at the head of the partition
    new_k, new_c, n_carry = compact(carry_k, carry_c)
    log_keys = jax.lax.dynamic_update_index_in_dim(
        state.log_keys, new_k, p, 0)
    log_counts = jax.lax.dynamic_update_index_in_dim(
        state.log_counts, new_c, p, 0)
    stats = state.stats._replace(
        tile_loads=state.stats.tile_loads + k,
        tile_stores=state.stats.tile_stores + k,
        merges=state.stats.merges + 1,
        carried=state.stats.carried + n_carried)
    return state._replace(log_keys=log_keys, log_counts=log_counts,
                          log_ptr=state.log_ptr.at[p].set(n_carry),
                          stats=stats)


# ---------------------------------------------------------------------------
# query-side scan (change segment + overflow, shared across a batch)
# ---------------------------------------------------------------------------
def scan_segment(seg_keys, seg_counts, q, chunk: int = 1024):
    """Masked linear scan of a log/overflow segment for a query batch.

    One scan serves the whole batch (the ``(Q, chunk)`` compare is shared
    across every query), so batched lookups pay the change-segment read
    once rather than per key. The segment is EMPTY-padded up to a chunk
    multiple: ``dynamic_slice`` clamps out-of-range starts, so an
    unpadded non-multiple tail would re-read (and double-count) the
    overlap with the previous chunk.
    """
    cap = seg_keys.shape[0]
    chunk = min(chunk, cap)
    pad = -cap % chunk
    if pad:
        seg_keys = jnp.concatenate(
            [seg_keys, jnp.full((pad,), EMPTY, seg_keys.dtype)])
        seg_counts = jnp.concatenate(
            [seg_counts, jnp.zeros((pad,), seg_counts.dtype)])
    n_chunks = (cap + pad) // chunk

    def body(i, acc):
        lk = jax.lax.dynamic_slice(seg_keys, (i * chunk,), (chunk,))
        lc = jax.lax.dynamic_slice(seg_counts, (i * chunk,), (chunk,))
        m = (q[:, None] == lk[None, :]) & (lk[None, :] != EMPTY)
        return acc + jnp.sum(m * lc[None, :], axis=1, dtype=jnp.int32)

    return jax.lax.fori_loop(0, n_chunks,
                             body, jnp.zeros(q.shape, jnp.int32))
