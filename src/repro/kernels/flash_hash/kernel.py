"""Pallas TPU kernels for the flash-hash counting table.

TPU adaptation of the paper's block-level update (§2.1): the HBM-resident
data segment is tiled one block per grid step — one *flash block* == one
VMEM tile. The grid walks blocks in ascending order (the paper's
*semi-random write* discipline → in-order single-store tiles), each tile is
read and written exactly once per merge (the paper's one-clean-per-block
property), and all probing math inside the tile is vectorized compare/min
over the lane dimension — no scatter, no per-element HBM traffic.

Kernels
-------
* ``merge``       — grid over all blocks; per block, fold its update list
  into the tile with vectorized cyclic linear probing. A list holds its
  updates first and EMPTY padding after (the layout ``ops.bucket_rows``
  builds), and the fold stops at the first EMPTY, so a block with no
  updates costs its tile's DMA and no loop steps.
* ``merge_dirty`` — beyond-paper variant: grid only over *dirty* blocks via
  a scalar-prefetched block-id list (saves the read+write of clean tiles —
  on-device analogue of "only merge blocks with staged updates").
* ``query``       — block-table indirection: scalar-prefetched block ids
  pick the tile each query batch reads (PagedAttention-style indexing).
* ``filter_probe_grid`` — negative-lookup pre-pass (DESIGN.md §12): each
  grid step holds one block's blocked-Bloom filter row (a few uint32
  lanes, ~64× smaller than the tile) and answers membership for up to
  ``qcap`` queries without touching the tile. Both merge kernels OR the
  inserted keys' Bloom bits into the filter row of exactly the dirty
  blocks they visit, in the same tile pass.

Tile layout
-----------
Every per-block array is walked as ``(rows, 1, width)`` with a
``(1, 1, width)`` block: the block's last two dims equal the array's, which
is what Mosaic requires of a one-row block (a ``(1, width)`` block of a
``(rows, width)`` array is refused — the sublane dim must be a multiple of
8 or the whole dim). On the TPU that array is laid out ``T(1,128)``: no
padding, one contiguous row per block. The table state is kept in this
layout (:func:`segments.init_state`); entry points also accept plain
``(rows, width)`` arrays and return outputs in the shapes they were given.
Per-update and per-query keys are read as scalars from SMEM windows of
the same shape — Mosaic has no dynamic lane extract from a vector.

Kernels run compiled on the TPU and in the Pallas interpreter on the CPU
(:func:`repro.kernels.pallas.pallas_call` decides, from the platform).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.hashing import Pow2Hash, bloom_positions
from ..pallas import pallas_call

EMPTY = -1


def _tiles(x):
    """View ``(rows, width)`` as the ``(rows, 1, width)`` tile layout (a
    no-op for arrays already in it)."""
    return x.reshape(x.shape[0], 1, x.shape[-1])


def _row(width: int, index_map, smem: bool = False) -> pl.BlockSpec:
    """One ``(1, 1, width)`` row per grid step (VMEM, or SMEM for rows the
    kernel reads scalar by scalar)."""
    return pl.BlockSpec((1, 1, width), index_map,
                        memory_space=pltpu.SMEM if smem else None)


def _bloom_or_row(filt, aw, u, valid, bits_log2):
    """OR one key's Bloom bits into a ``(1, fw)`` filter row.

    ``aw`` is the lane iota over the row, ``u`` the key as uint32. All
    lane-parallel select/shift — no scatter."""
    for p in bloom_positions(u, bits_log2):
        w = (p >> jnp.uint32(5)).astype(jnp.int32)
        mask = jnp.left_shift(jnp.uint32(1), p & jnp.uint32(31))
        filt = jnp.where((aw == w) & valid, filt | mask, filt)
    return filt


def _bloom_test_row(filt, aw, u, bits_log2):
    """Test one key against a ``(1, fw)`` filter row (k-probe AND).

    ``filt`` is the row bit-cast to int32: Mosaic reduces no unsigned
    ints, and the one-lane select-and-sum extracts the word unchanged."""
    hit = jnp.int32(1)
    for p in bloom_positions(u, bits_log2):
        w = (p >> jnp.uint32(5)).astype(jnp.int32)
        word = jnp.sum(jnp.where(aw == w, filt, 0))
        hit &= (word >> (p & jnp.uint32(31)).astype(jnp.int32)) & 1
    return hit != 0


# --------------------------------------------------------------------------
# merge kernel
# --------------------------------------------------------------------------
def _merge_kernel(pair: Pow2Hash, tk_ref, tc_ref, tf_ref, uk_ref, uc_ref,
                  ok_ref, oc_ref, of_ref, sk_ref, sc_ref):
    r = tk_ref.shape[2]
    fw = tf_ref.shape[2]
    max_u = uk_ref.shape[2]
    keys0 = tk_ref[0]            # (1, r) int32 tile in VMEM
    counts0 = tc_ref[0]
    filt0 = tf_ref[0]            # (1, fw) uint32 blocked-Bloom filter row
    ar = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    aw = jax.lax.broadcasted_iota(jnp.int32, (1, fw), 1)
    au = jax.lax.broadcasted_iota(jnp.int32, (1, max_u), 1)
    inf = jnp.int32(r + 1)
    rmask = jnp.int32(r - 1)
    fbits_log2 = (fw * 32).bit_length() - 1

    def body(j, carry):
        keys, counts, filt, spill_k, spill_c, n_spill = carry
        k = uk_ref[0, 0, j]                          # scalars from SMEM
        c = uc_ref[0, 0, j]
        valid = k != EMPTY
        home = (pair.g(k) & rmask).astype(jnp.int32)
        d = (ar - home) & rmask                      # cyclic probe distance
        d_match = jnp.min(jnp.where(keys == k, d, inf))
        d_empty = jnp.min(jnp.where(keys == EMPTY, d, inf))
        d_tgt = jnp.minimum(d_match, d_empty)
        found = valid & (d_tgt < inf)
        hit = (d == d_tgt) & found                   # one-hot over the tile
        is_insert = d_empty < d_match
        keys = jnp.where(hit & is_insert, k, keys)
        counts = jnp.where(hit, counts + c, counts)
        # every valid update key gets its filter bits — including spills,
        # whose home block is this one (queries consult the home filter)
        filt = _bloom_or_row(filt, aw, k.astype(jnp.uint32), valid,
                             fbits_log2)
        do_spill = valid & ~found
        s_hit = (au == n_spill) & do_spill
        spill_k = jnp.where(s_hit, k, spill_k)
        spill_c = jnp.where(s_hit, c, spill_c)
        n_spill = n_spill + do_spill.astype(jnp.int32)
        return keys, counts, filt, spill_k, spill_c, n_spill

    def more(carry):           # updates are front-packed: stop at EMPTY
        j = carry[0]
        return (j < max_u) & (uk_ref[0, 0, jnp.minimum(j, max_u - 1)]
                              != EMPTY)

    def step(carry):
        return (carry[0] + 1,) + body(carry[0], carry[1:])

    init = (jnp.int32(0), keys0, counts0, filt0,
            jnp.full((1, max_u), EMPTY, jnp.int32),
            jnp.zeros((1, max_u), counts0.dtype),
            jnp.int32(0))
    _, keys, counts, filt, spill_k, spill_c, _ = jax.lax.while_loop(
        more, step, init)
    ok_ref[0] = keys
    oc_ref[0] = counts
    of_ref[0] = filt
    sk_ref[0] = spill_k
    sc_ref[0] = spill_c


@functools.partial(jax.jit, static_argnums=(0,))
def merge(pair: Pow2Hash, table_keys, table_counts, filter_words,
          upd_keys, upd_counts):
    """Merge bucketed updates into the data segment.

    table_keys/table_counts: (n_b, r) int32 (or the ``(n_b, 1, r)`` tile
    layout); filter_words: (n_b, fw) uint32 blocked-Bloom filter rows;
    upd_keys/upd_counts: (n_b, max_u) int32, each row's updates first,
    then EMPTY padding (entries after a row's first EMPTY are ignored).
    Returns (new_keys, new_counts, new_filter, spill_keys, spill_counts),
    each shaped like the matching input.
    """
    args = [table_keys, table_counts, filter_words, upd_keys, upd_counts]
    tiles = [_tiles(a) for a in args]
    n_b, r = table_keys.shape[0], table_keys.shape[-1]
    fw = filter_words.shape[-1]
    max_u = upd_keys.shape[-1]
    blk = lambda b: (b, 0, 0)
    specs = [_row(r, blk), _row(r, blk), _row(fw, blk)]
    outs = pallas_call(
        functools.partial(_merge_kernel, pair),
        grid=(n_b,),
        in_specs=specs + [_row(max_u, blk, smem=True)] * 2,
        out_specs=specs + [_row(max_u, blk)] * 2,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles],
        input_output_aliases={0: 0, 1: 1, 2: 2},   # in-place tile update
        name="flash_hash_merge",
    )(*tiles)
    return [o.reshape(a.shape) for o, a in zip(outs, args)]


# --------------------------------------------------------------------------
# dirty-only merge (beyond-paper §Perf optimization)
# --------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnums=(0,))
def merge_dirty(pair: Pow2Hash, table_keys, table_counts, filter_words,
                dirty_blocks, upd_keys, upd_counts):
    """Like :func:`merge`, but the grid only visits ``dirty_blocks``.

    dirty_blocks: (n_d,) int32 distinct block ids (a repeated id would
    re-read a tile whose write-back may still be in flight).
    upd_keys/upd_counts: (n_d, max_u) updates for the listed blocks.
    The filter rows of exactly the dirty blocks are OR-updated in the same
    pass; clean blocks' rows pass through untouched via the aliasing.
    """
    args = [table_keys, table_counts, filter_words, upd_keys, upd_counts]
    tiles = [_tiles(a) for a in args]
    r = table_keys.shape[-1]
    fw = filter_words.shape[-1]
    n_d, max_u = upd_keys.shape[0], upd_keys.shape[-1]

    def kern(blocks_ref, *refs):  # scalar-prefetch ref only feeds index_maps
        del blocks_ref
        _merge_kernel(pair, *refs)

    tile = lambda i, blocks: (blocks[i], 0, 0)
    upd = lambda i, blocks: (i, 0, 0)
    specs = [_row(r, tile), _row(r, tile), _row(fw, tile)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_d,),
        in_specs=specs + [_row(max_u, upd, smem=True)] * 2,
        out_specs=specs + [_row(max_u, upd)] * 2,
    )
    outs = pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles],
        input_output_aliases={1: 0, 2: 1, 3: 2},  # offset by scalar-prefetch
        name="flash_hash_merge_dirty",
    )(dirty_blocks, *tiles)
    return [o.reshape(a.shape) for o, a in zip(outs, args)]


# --------------------------------------------------------------------------
# query kernel (block-table indirection)
# --------------------------------------------------------------------------
def _query_kernel(pair: Pow2Hash, blocks_ref, qk_ref, tk_ref, tc_ref,
                  cnt_ref, dist_ref):
    del blocks_ref  # only used by the index_map
    r = tk_ref.shape[2]
    qchunk = qk_ref.shape[2]
    keys = tk_ref[0]
    counts = tc_ref[0]
    ar = jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    au = jax.lax.broadcasted_iota(jnp.int32, (1, qchunk), 1)
    inf = jnp.int32(r + 1)
    rmask = jnp.int32(r - 1)

    def one(j, carry):
        cnts, dists = carry
        k = qk_ref[0, 0, j]                           # scalar from SMEM
        home = (pair.g(k) & rmask).astype(jnp.int32)
        d = (ar - home) & rmask
        d_match = jnp.min(jnp.where(keys == k, d, inf))
        d_empty = jnp.min(jnp.where(keys == EMPTY, d, inf))
        found = d_match < d_empty
        hit = (d == d_match) & found
        cnt = jnp.sum(jnp.where(hit, counts, 0))
        dist = jnp.where(found, d_match, jnp.minimum(d_empty, r - 1)) + 1
        sel = au == j
        cnts = jnp.where(sel, cnt, cnts)
        dists = jnp.where(sel, dist, dists)
        return cnts, dists

    cnts0 = jnp.zeros((1, qchunk), counts.dtype)
    dists0 = jnp.zeros((1, qchunk), jnp.int32)
    cnts, dists = jax.lax.fori_loop(0, qchunk, one, (cnts0, dists0))
    cnt_ref[0] = cnts
    dist_ref[0] = dists


@functools.partial(jax.jit, static_argnums=(0,))
def query_grid(pair: Pow2Hash, table_keys, table_counts, blocks, q2):
    """Point queries over an explicit chunk layout (the batched entry).

    q2: (n_rows, qcap) int32 — grid step ``i`` reads the tile of block
    ``blocks[i]`` once and answers all of row ``i``'s queries against it,
    so a row **must** only hold keys whose ``s()`` is ``blocks[i]``
    (callers bucket; :func:`ops.query_blocked` builds this layout).
    Padding lanes (``EMPTY`` or foreign-block keys) produce junk values
    that callers never gather. Sized for large batches: HBM tile traffic
    is one read per *queried block*, not one per query/chunk.
    Returns (counts, distances) shaped like ``q2``."""
    r = table_keys.shape[-1]
    n_rows, qcap = q2.shape[0], q2.shape[-1]
    row = lambda i, blocks: (i, 0, 0)
    tile = lambda i, blocks: (blocks[i], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rows,),
        in_specs=[_row(qcap, row, smem=True), _row(r, tile), _row(r, tile)],
        out_specs=[_row(qcap, row), _row(qcap, row)],
    )
    cnts, dists = pallas_call(
        functools.partial(_query_kernel, pair),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_rows, 1, qcap), table_counts.dtype),
            jax.ShapeDtypeStruct((n_rows, 1, qcap), jnp.int32),
        ],
        name="flash_hash_query",
    )(blocks.astype(jnp.int32), _tiles(q2), _tiles(table_keys),
      _tiles(table_counts))
    return cnts.reshape(q2.shape), dists.reshape(q2.shape)


@functools.partial(jax.jit, static_argnums=(0, 4))
def query(pair: Pow2Hash, table_keys, table_counts, q_keys,
          qchunk: int = 128):
    """Point queries. q_keys: (Q,) int32, Q % qchunk == 0. Queries must be
    pre-sorted so that each chunk hits one block (callers use
    ``ops.query``, which sorts/buckets); here each chunk's block id is the
    block of its first key — keys in a chunk from other blocks return junk,
    so ops-level bucketing pads chunks with the chunk's own block keys."""
    (Q,) = q_keys.shape
    assert Q % qchunk == 0
    n_chunks = Q // qchunk
    q2 = q_keys.reshape(n_chunks, qchunk)
    blocks = pair.s(q2[:, 0]).astype(jnp.int32)    # (n_chunks,)
    cnts, dists = query_grid(pair, table_keys, table_counts, blocks, q2)
    return cnts.reshape(Q), dists.reshape(Q)


# --------------------------------------------------------------------------
# blocked-Bloom filter probe (negative-lookup pre-pass, DESIGN.md §12)
# --------------------------------------------------------------------------
def _filter_probe_kernel(blocks_ref, qk_ref, tf_ref, may_ref):
    del blocks_ref  # only used by the index_map
    fw = tf_ref.shape[2]
    qchunk = qk_ref.shape[2]
    filt = jax.lax.bitcast_convert_type(tf_ref[0], jnp.int32)  # (1, fw)
    aw = jax.lax.broadcasted_iota(jnp.int32, (1, fw), 1)
    au = jax.lax.broadcasted_iota(jnp.int32, (1, qchunk), 1)
    fbits_log2 = (fw * 32).bit_length() - 1

    def one(j, may):
        k = qk_ref[0, 0, j]                       # scalar from SMEM
        hit = _bloom_test_row(filt, aw, k.astype(jnp.uint32), fbits_log2)
        ok = (k != EMPTY) & hit
        return jnp.where(au == j, ok.astype(jnp.int32), may)

    may_ref[0] = jax.lax.fori_loop(
        0, qchunk, one, jnp.zeros((1, qchunk), jnp.int32))


@jax.jit
def filter_probe_grid(filter_words, blocks, q2):
    """Membership pre-pass over the same chunk layout as :func:`query_grid`.

    Grid step ``i`` holds only block ``blocks[i]``'s filter row — a few
    uint32 lanes, ~``r/fw`` times smaller than the tile — and answers all
    of row ``i``'s queries against it with zero tile traffic. Returns an
    int32 mask shaped like ``q2``: 0 ⇒ the key is
    definitively absent from the block (and, because staging paths also
    maintain the filter, from the change segment and overflow region
    too); 1 ⇒ maybe present, fetch the tile. Rows must be bucketed like
    :func:`query_grid`'s (``ops.query_blocked`` builds both layouts);
    the Bloom hash ignores the block id, so foreign-lane junk is
    harmless — callers never gather those lanes."""
    fw = filter_words.shape[-1]
    n_rows, qcap = q2.shape[0], q2.shape[-1]
    row = lambda i, blocks: (i, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_rows,),
        in_specs=[_row(qcap, row, smem=True),
                  _row(fw, lambda i, blocks: (blocks[i], 0, 0))],
        out_specs=[_row(qcap, row)],
    )
    (may,) = pallas_call(
        _filter_probe_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, 1, qcap), jnp.int32)],
        name="flash_hash_filter_probe",
    )(blocks.astype(jnp.int32), _tiles(q2), _tiles(filter_words))
    return may.reshape(q2.shape)
