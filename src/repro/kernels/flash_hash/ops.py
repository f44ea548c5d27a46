"""jit'd wrappers around the flash-hash Pallas kernels.

Adds the outside-the-kernel plumbing the paper's schemes need:

* ``bucket_rows`` — generic drain: pack staged updates into the dense
  ``(n_rows, max_u)`` layout the merge kernels tile over, given an
  arbitrary destination-row assignment (block id for a full merge, grid
  position for a dirty-permutation merge, partition-local offset for an
  MDB partition drain). Updates beyond a row's ``max_u`` capacity are
  *carried over* (returned, stay staged) — the deferred-update discipline
  that bounds VMEM per tile.
* ``bucket_updates`` — RAM-buffer drain: ``bucket_rows`` with rows =
  destination block (the secondary hash ``s``).
* ``accumulate`` — the TPU-native RAM buffer: sort + segment-sum dedup of a
  token batch into (unique key, count) pairs (open-hash pre-aggregation).
* ``merge`` / ``merge_dirty`` — merge kernel entry points.
* ``query_sorted`` / ``query_blocked`` — per-key vs batched query entry
  points (the latter buckets the batch by block so each queried tile is
  fetched once per wave).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ...core.hashing import Pow2Hash
from . import kernel as _k

EMPTY = _k.EMPTY


@functools.partial(jax.jit, static_argnums=(3, 4))
@jax.named_scope("bucket_rows")
def bucket_rows(rows, keys, counts, n_rows: int, max_u: int):
    """Pack (keys, counts) updates into (n_rows, max_u) per-row buffers.

    ``rows`` is the destination row per update — for a full-table merge it
    is the block id ``s(key)``; for a dirty-block merge it is the key's
    position in the dirty-block list; for an MDB partition drain it is the
    block offset within the partition. Entries with ``rows`` outside
    ``[0, n_rows)`` or ``key == EMPTY`` are padding and dropped.

    Returns (upd_keys, upd_counts, carry_keys, carry_counts, n_carried):
    carry_* hold updates that exceeded a row's ``max_u`` capacity (sparse,
    same (U,) layout, EMPTY-padded) and must stay staged.
    """
    (U,) = keys.shape
    valid = (keys != EMPTY) & (rows >= 0) & (rows < n_rows)
    rw = jnp.where(valid, rows, n_rows).astype(jnp.int32)
    order = jnp.argsort(rw, stable=True)
    sk = keys[order]
    sc = counts[order]
    sr = rw[order]
    # position within the row's group
    start = jnp.searchsorted(sr, jnp.arange(n_rows + 1, dtype=sr.dtype))
    pos_in_r = jnp.arange(U, dtype=jnp.int32) - start[jnp.clip(sr, 0, n_rows)]
    keep = (sr < n_rows) & (pos_in_r < max_u)
    row = jnp.where(keep, sr, n_rows)  # out-of-bounds rows get dropped
    upd_keys = jnp.full((n_rows, max_u), EMPTY, dtype=keys.dtype)
    upd_counts = jnp.zeros((n_rows, max_u), dtype=counts.dtype)
    col = jnp.where(keep, pos_in_r, 0)
    upd_keys = upd_keys.at[row, col].set(sk, mode="drop")
    upd_counts = upd_counts.at[row, col].set(sc, mode="drop")
    carried = (sr < n_rows) & ~keep
    carry_keys = jnp.where(carried, sk, EMPTY)
    carry_counts = jnp.where(carried, sc, 0)
    return (upd_keys, upd_counts, carry_keys, carry_counts,
            carried.sum(dtype=jnp.int32))


@functools.partial(jax.jit, static_argnums=(0, 3))
def bucket_updates(pair: Pow2Hash, keys, counts, max_u: int):
    """Pack (keys, counts) updates into (n_b, max_u) per-block buffers.

    keys/counts: (U,) int32; EMPTY-keyed entries are padding and dropped.
    Returns (upd_keys, upd_counts, carry_keys, carry_counts, n_carried):
    carry_* hold updates that exceeded a block's capacity (sparse, same
    (U,) layout, EMPTY-padded).
    """
    n_b = pair.num_slots
    rows = jnp.where(keys != EMPTY, pair.s(keys), n_b).astype(jnp.int32)
    return bucket_rows(rows, keys, counts, n_b, max_u)


@jax.jit
def accumulate(tokens) -> Tuple[jax.Array, jax.Array]:
    """Open-hash RAM buffer, TPU-native: dedup a batch into (keys, counts).

    tokens: (T,) int32 (EMPTY entries ignored). Returns (T,)-shaped unique
    keys (EMPTY-padded) + int32 counts: sort, then segment-sum runs.
    """
    t = jnp.sort(tokens)
    is_head = jnp.concatenate([jnp.ones((1,), bool), t[1:] != t[:-1]])
    is_head &= t != EMPTY
    seg = jnp.cumsum(is_head) - 1                     # run ids
    ones = (t != EMPTY).astype(jnp.int32)
    counts = jax.ops.segment_sum(ones, seg, num_segments=t.shape[0])
    heads_idx = jnp.where(is_head, jnp.arange(t.shape[0]), t.shape[0] - 1)
    # compact run heads to the front, EMPTY-pad the tail
    order = jnp.argsort(jnp.where(is_head, 0, 1), stable=True)
    keys = jnp.where(is_head[order], t[order], EMPTY)
    cnts = jnp.where(is_head[order],
                     counts[jnp.clip(seg[order], 0, t.shape[0] - 1)], 0)
    return keys, cnts.astype(jnp.int32)


merge = _k.merge
merge_dirty = _k.merge_dirty


@functools.partial(jax.jit, static_argnums=(0,))
def query_sorted(pair: Pow2Hash, table_keys, table_counts, q_keys):
    """Point queries; sorts by block first so consecutive grid steps reuse
    the same VMEM tile (Pallas elides the re-fetch), then unsorts.

    One grid step per query — the per-key reference path. Batches should
    use :func:`query_blocked`, which fetches each queried tile once."""
    blk = pair.s(q_keys)
    order = jnp.argsort(blk, stable=True)
    cnts, dists = _k.query(pair, table_keys, table_counts, q_keys[order], 1)
    inv = jnp.argsort(order, stable=True)
    return cnts[inv], dists[inv]


@functools.partial(jax.jit, static_argnums=(0, 4))
def query_blocked_ex(pair: Pow2Hash, table_keys, table_counts, q_keys,
                     qcap: int = 128, filter_words=None):
    """Batched point queries, sized for large batches (paper §2.7).

    Buckets the batch by destination block into the dense
    ``(n_rows, qcap)`` layout :func:`kernel.query_grid` tiles over, with
    one row per *queried* block (``n_rows = min(n_b, Q)`` rows
    statically; unqueried blocks get no row, surplus rows all point at
    block 0, which consecutive-step Pallas tile reuse makes near-free).
    One *wave* answers up to ``qcap`` queries per block with a
    single tile fetch per queried block, instead of one grid step per
    query. Blocks holding more than ``qcap`` queries drain over
    additional waves (``fori_loop``; with deduped batches one wave is
    the common case).

    With ``filter_words`` (the ``(n_b, fw)`` blocked-Bloom rows from
    ``state.filter_words``), a :func:`kernel.filter_probe_grid` pre-pass
    tests every key against its block's SMEM-resident filter row first
    and the survivors are *re-bucketed*: blocks whose queries were all
    definite misses drop out of the queried-block list entirely, so they
    cost no tile fetch, and the post-filter ``max_load`` shrinks the
    wave count (an all-filtered batch runs zero query waves). Filtered
    keys answer ``(0, 0)``.

    q_keys: (Q,) int32, ``EMPTY`` entries are padding and return
    ``(0, 0)``. Returns (counts, probe_distances, n_tiles) with the first
    two aligned with ``q_keys`` — bit-identical to :func:`query_sorted`
    for valid unfiltered keys — and ``n_tiles`` the number of distinct
    block tiles the query waves fetched (the batch's accounted
    ``tile_loads``; 0 when the filter killed everything).
    """
    n_b = table_keys.shape[0]
    (Q,) = q_keys.shape
    if Q == 0:
        return (jnp.zeros((0,), table_counts.dtype),
                jnp.zeros((0,), jnp.int32), jnp.zeros((), jnp.int32))
    qcap = max(min(qcap, Q), 1)
    n_rows = min(n_b, Q)       # ≤ Q distinct blocks can be queried
    q = q_keys.astype(jnp.int32)
    valid = q != EMPTY

    def bucket(alive):
        blk = jnp.where(alive, pair.s(q), n_b).astype(jnp.int32)
        order = jnp.argsort(blk, stable=True)
        sq, sb = q[order], blk[order]
        start = jnp.searchsorted(sb, jnp.arange(n_b + 1, dtype=sb.dtype))
        pos = jnp.arange(Q, dtype=jnp.int32) - start[jnp.clip(sb, 0, n_b)]
        max_load = jnp.max(start[1:] - start[:-1])  # fullest block's queries
        # dense rank of each query's block within the queried-block set
        is_first = (sb < n_b) & jnp.concatenate(
            [jnp.ones((1,), bool), sb[1:] != sb[:-1]])
        rank = jnp.cumsum(is_first) - 1
        grid_blocks = jnp.zeros((n_rows,), jnp.int32).at[
            jnp.where(is_first, rank, n_rows)].set(sb, mode="drop")
        return order, sq, sb, pos, max_load, is_first, rank, grid_blocks

    order, sq, sb, pos, max_load, is_first, rank, grid_blocks = bucket(valid)

    def dense_rows(p, sb, pos, rank, sq):
        win = (sb < n_b) & (pos >= p * qcap) & (pos < (p + 1) * qcap)
        row = jnp.where(win, rank, n_rows)
        col = jnp.where(win, pos - p * qcap, 0)
        dense = jnp.full((n_rows, qcap), EMPTY, jnp.int32
                         ).at[row, col].set(sq, mode="drop")
        g = (jnp.clip(rank, 0, n_rows - 1),
             jnp.clip(pos - p * qcap, 0, qcap - 1))
        return win, dense, g

    if filter_words is not None:
        def fwave(p, may_s):
            win, dense, g = dense_rows(p, sb, pos, rank, sq)
            m = _k.filter_probe_grid(filter_words, grid_blocks, dense)
            return jnp.where(win, m[g], may_s)

        n_fwaves = (max_load + qcap - 1) // qcap
        with jax.named_scope("filter_pass"):
            may_s = jax.lax.fori_loop(0, n_fwaves, fwave,
                                      jnp.zeros((Q,), jnp.int32))
        may = jnp.zeros((Q,), jnp.int32).at[order].set(may_s)
        # re-bucket the survivors: fully-filtered blocks vanish from the
        # grid list (no tile fetch) and the post-filter max_load shrinks
        # the wave loop — possibly to zero waves
        order, sq, sb, pos, max_load, is_first, rank, grid_blocks = bucket(
            valid & (may > 0))

    n_tiles = is_first.sum(dtype=jnp.int32)

    def wave(p, acc):
        cnt_s, dist_s = acc
        win, dense, g = dense_rows(p, sb, pos, rank, sq)
        c, d = _k.query_grid(pair, table_keys, table_counts, grid_blocks,
                             dense)
        cnt_s = jnp.where(win, c[g], cnt_s)
        dist_s = jnp.where(win, d[g], dist_s)
        return cnt_s, dist_s

    n_waves = (max_load + qcap - 1) // qcap
    with jax.named_scope("query_waves"):
        cnt_s, dist_s = jax.lax.fori_loop(
            0, n_waves, wave,
            (jnp.zeros((Q,), table_counts.dtype),
             jnp.zeros((Q,), jnp.int32)))
    cnts = jnp.zeros((Q,), table_counts.dtype).at[order].set(cnt_s)
    dists = jnp.zeros((Q,), jnp.int32).at[order].set(dist_s)
    return cnts, dists, n_tiles


def query_blocked(pair: Pow2Hash, table_keys, table_counts, q_keys,
                  qcap: int = 128, filter_words=None):
    """:func:`query_blocked_ex` without the tile count (compat entry)."""
    cnts, dists, _ = query_blocked_ex(pair, table_keys, table_counts,
                                      q_keys, qcap, filter_words)
    return cnts, dists
