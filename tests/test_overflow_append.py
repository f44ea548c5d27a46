"""The overflow append that closes every merge.

``segments.append_overflow`` compacts the merge kernel's front-packed
``(n_rows, 1, max_u)`` spill into the overflow region by per-row counts.
These tests hold it to a NumPy reference (and to the pointer-bumped
``scatter_rows`` formulation it replaced), and hold the merge kernels to
the front-packing it relies on.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import segments
from repro.core.hashing import Pow2Hash, filter_words_for
from repro.kernels.flash_hash import ops

EMPTY = segments.EMPTY
CAP = 32          # overflow capacity of every case


def _reference(ov_k, ov_c, ov_ptr, dropped, spill_k, spill_c):
    """Row-major compaction of the non-EMPTY spill, appended at
    ``ov_ptr``; what does not fit is counted as dropped."""
    ov_k, ov_c = ov_k.copy(), ov_c.copy()
    flat_k = spill_k.reshape(-1)
    flat_c = spill_c.reshape(-1)
    live = flat_k != EMPTY
    keys, cnts = flat_k[live], flat_c[live]
    n_fit = max(0, min(len(keys), len(ov_k) - ov_ptr))
    ov_k[ov_ptr:ov_ptr + n_fit] = keys[:n_fit]
    ov_c[ov_ptr:ov_ptr + n_fit] = cnts[:n_fit]
    return ov_k, ov_c, ov_ptr + n_fit, dropped + len(keys) - n_fit


def _scatter_rows_append(state, spill_k, spill_c):
    """The former append: one pointer-bumped row through scatter_rows."""
    flat_k = spill_k.reshape(-1)
    ov_k, ov_c, ptrs, rest_k, _, _ = segments.scatter_rows(
        state.ov_keys[None, :], state.ov_counts[None, :], state.ov_ptr[None],
        jnp.zeros(flat_k.shape, jnp.int32), flat_k, spill_c.reshape(-1))
    return (ov_k[0], ov_c[0], ptrs[0],
            state.stats.dropped + (rest_k != EMPTY).sum(dtype=jnp.int32))


def _spill(n_rows, max_u, per_row, seed):
    """A front-packed spill: row i holds per_row[i] entries, then EMPTY."""
    rng = np.random.default_rng(seed)
    sk = np.full((n_rows, 1, max_u), EMPTY, np.int32)
    sc = np.zeros((n_rows, 1, max_u), np.int32)
    for i, n in per_row.items():
        sk[i, 0, :n] = rng.integers(0, 1 << 30, n)
        sc[i, 0, :n] = rng.integers(-5, 100, n)
    return sk, sc


# (n_rows, max_u, spilling rows {row: entries}, ov_ptr)
CASES = {
    "no_spill": (64, 16, {}, 5),
    "a_few_rows": (64, 16, {0: 1, 7: 3, 8: 2, 63: 16}, 4),
    "fills_exactly": (64, 16, {3: 10, 20: 7, 41: 5}, 10),
    "overflows": (64, 16, {1: 16, 2: 16, 30: 9, 62: 4}, 6),
    "ptr_at_capacity": (64, 16, {5: 4, 9: 1}, CAP),
    "mdb_partition": (4, 16, {0: 2, 2: 16, 3: 1}, 0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_append_overflow_matches_reference(case):
    n_rows, max_u, per_row, ov_ptr = CASES[case]
    sk, sc = _spill(n_rows, max_u, per_row, seed=len(case))
    rng = np.random.default_rng(1)
    # the region before ov_ptr is live; past it, sentinels that must
    # survive wherever nothing is appended
    ov_k = rng.integers(0, 1 << 30, CAP).astype(np.int32)
    ov_c = rng.integers(1, 50, CAP).astype(np.int32)
    ov_k[ov_ptr:] = -7 - np.arange(CAP - ov_ptr)
    ov_c[ov_ptr:] = -3
    dropped = 2
    state = segments.init_state(4, 8, (8,), (), CAP, 1)
    state = state._replace(
        ov_keys=jnp.asarray(ov_k), ov_counts=jnp.asarray(ov_c),
        ov_ptr=jnp.int32(ov_ptr),
        stats=state.stats._replace(dropped=jnp.int32(dropped)))

    got = segments.append_overflow(state, jnp.asarray(sk), jnp.asarray(sc))
    got = (got.ov_keys, got.ov_counts, got.ov_ptr, got.stats.dropped)
    want = _reference(ov_k, ov_c, ov_ptr, dropped, sk, sc)
    old = _scatter_rows_append(state, jnp.asarray(sk), jnp.asarray(sc))
    for g, w, o in zip(got, want, old):
        assert np.asarray(g).dtype == np.int32
        np.testing.assert_array_equal(np.asarray(g), w)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o))
    total = sum(per_row.values())
    if case == "fills_exactly":
        assert total == CAP - ov_ptr and int(got[2]) == CAP
        assert int(got[3]) == dropped
    if case == "overflows":
        assert int(got[3]) == dropped + total - (CAP - ov_ptr)


def _keys_in_block(pair, block, n, start=0):
    keys, x = [], start
    while len(keys) < n:
        if int(pair.s(x)) == block:
            keys.append(x)
        x += 1
    return keys


@pytest.mark.parametrize("entry", ["merge", "merge_dirty"])
def test_merge_kernel_spill_rows_are_front_packed(entry):
    """Each spill row holds its entries in columns [0, n_i), EMPTY after,
    also where updates that hit resident keys sit between the spills."""
    pair = Pow2Hash(q_log2=6, r_log2=3)      # 8 blocks of 8 slots
    n_b, r, max_u = pair.num_slots, pair.r, 16
    fw = filter_words_for(r)
    tk = jnp.full((n_b, r), EMPTY, jnp.int32)
    tc = jnp.zeros((n_b, r), jnp.int32)
    tf = jnp.zeros((n_b, fw), jnp.uint32)
    # block 0 already holds 5 keys; block 3 is empty
    b0 = _keys_in_block(pair, 0, 14)
    b3 = _keys_in_block(pair, 3, 13)
    uk = np.full((n_b, max_u), EMPTY, np.int32)
    uc = np.zeros((n_b, max_u), np.int32)
    uk[0, :5], uc[0, :5] = b0[:5], 1
    tk, tc, tf, sk, _ = ops.merge(pair, tk, tc, tf, jnp.asarray(uk),
                                  jnp.asarray(uc))
    assert int((sk != EMPTY).sum()) == 0
    # block 0: 3 free slots, 9 new keys interleaved with 3 resident ones
    # -> 6 spills; block 3: 13 new keys into 8 slots -> 5 spills
    upd0 = [b0[5], b0[0], b0[6], b0[7], b0[1], b0[8], b0[9], b0[10],
            b0[2], b0[11], b0[12], b0[13]]
    uk = np.full((n_b, max_u), EMPTY, np.int32)
    uc = np.zeros((n_b, max_u), np.int32)
    uk[0, :12], uc[0, :12] = upd0, np.arange(1, 13)
    uk[3, :13], uc[3, :13] = b3, 2
    if entry == "merge":
        _, _, _, sk, sc = ops.merge(pair, tk, tc, tf, jnp.asarray(uk),
                                    jnp.asarray(uc))
    else:
        dirty = jnp.asarray([0, 3], jnp.int32)
        _, _, _, sk, sc = ops.merge_dirty(pair, tk, tc, tf, dirty,
                                          jnp.asarray(uk[[0, 3]]),
                                          jnp.asarray(uc[[0, 3]]))
    sk, sc = np.asarray(sk), np.asarray(sc)
    n_i = (sk != EMPTY).sum(axis=-1)
    assert sorted(n_i[n_i > 0].tolist()) == [5, 6]
    cols = np.arange(max_u)
    np.testing.assert_array_equal(sk != EMPTY, cols < n_i[:, None])
    np.testing.assert_array_equal(sc[sk == EMPTY], 0)
