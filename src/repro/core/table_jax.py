"""Device-resident (JAX) counting hash table — the TPU-native twin of
:mod:`table_sim`, used by the framework's data-statistics, MoE-accounting
and serving layers.

Mapping (DESIGN.md §2): HBM table = data segment; ``sort+segment_sum``
dedup = RAM buffer; HBM append-log = change segment (monolithic for MDB-L,
partitioned for MDB); Pallas tile merge = block-level update. Stats
counters mirror the paper's ledger: ``tile_stores`` is the clean/wear
analogue (one per block rewrite).

This module is *scheme policy only* (DESIGN.md §3): when each of the
paper's three schemes stages, drains and merges. The segment state record
and every shared op (pointer-bumped staging, dirty-block merges, query
scans) live in :mod:`segments`; the host-side RAM buffer H_R in front of
this module is :mod:`write_engine`.

* ``MB``    — no change segment; every update batch is bucketed and merged
  immediately into the dirty blocks it touches.
* ``MDB``   — partitioned change segment: partition ``p`` buffers updates
  for the ``k`` consecutive data blocks ``[p*k, (p+1)*k)``; a full
  partition drains through a ``k``-block dirty merge (exactly ``k`` tile
  rewrites, not ``num_blocks``).
* ``MDB-L`` — monolithic log change segment; sequential appends; a full
  log drains through a dirty merge over only the blocks with staged keys.

Everything is functional: ``state -> op -> state`` and jit-friendly; the
scheme is a static config choice, so each policy compiles to its own
program. The ``update``/``flush`` entry points **donate** the incoming
state (DESIGN.md §7): the old state's buffers are reused in place rather
than copied — callers must rebind (``state = update(cfg, state, ...)``)
and never touch the donated value again.

Since the store's flush went asynchronous (DESIGN.md §9) donation happens
*off-thread*: the background drain worker is the only code allowed to
call the donated entry points while a drain is in flight, and it guards
every dispatch with :func:`segments.assert_live` (re-exported here as
``assert_live``) so a raced or reused state fails loudly instead of as
an opaque XLA deleted-buffer error.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.flash_hash import ops as hops
from . import segments as seg
from .hashing import Pow2Hash
from .hashing import filter_words_for as hashing_filter_words_for

EMPTY = seg.EMPTY

# re-exported state records: the segment layer owns them, the public API
# (and every existing consumer) reaches them through this module
TableStats = seg.TableStats
DeviceTableState = seg.DeviceTableState
accumulate_deltas = seg.accumulate_deltas
assert_live = seg.assert_live             # off-thread donation guard (§9)
_scan_segment = seg.scan_segment          # back-compat alias (tests)

_SCHEMES = ("MB", "MDB", "MDB-L")


@dataclasses.dataclass(frozen=True)
class FlashTableConfig:
    """Geometry + policy of a device table."""

    q_log2: int = 16              # total entries (power of two)
    r_log2: int = 10              # entries per block (≥128-lane friendly)
    scheme: str = "MDB-L"         # "MB" | "MDB" | "MDB-L"
    log_capacity: int = 1 << 14   # change-segment entries (MDB / MDB-L)
    cs_partitions: int = 8        # MDB: change-segment partitions
    max_updates_per_block: int = 1 << 9   # VMEM cap per tile merge
    overflow_capacity: int = 1 << 10
    filters: bool = True          # consult the blocked-Bloom filters on
                                  # lookups (§12). Maintenance always runs
                                  # (state invariants stay uniform); this
                                  # only gates the negative-lookup fast
                                  # path, so it can be toggled per table
                                  # for A/B benchmarks.

    def __post_init__(self):
        if self.scheme not in _SCHEMES:
            raise ValueError(f"unknown scheme {self.scheme!r}; "
                             f"expected one of {_SCHEMES}")
        if self.scheme == "MDB":
            if self.cs_partitions <= 0:
                raise ValueError("cs_partitions must be positive")
            if self.num_blocks % self.cs_partitions:
                raise ValueError(
                    f"cs_partitions={self.cs_partitions} must divide "
                    f"num_blocks={self.num_blocks}")
            if self.log_capacity % self.cs_partitions:
                raise ValueError(
                    f"cs_partitions={self.cs_partitions} must divide "
                    f"log_capacity={self.log_capacity}")

    @property
    def pair(self) -> Pow2Hash:
        return Pow2Hash(q_log2=self.q_log2, r_log2=self.r_log2)

    @property
    def num_blocks(self) -> int:
        return 1 << (self.q_log2 - self.r_log2)

    @property
    def block_entries(self) -> int:
        return 1 << self.r_log2

    @property
    def blocks_per_partition(self) -> int:
        """MDB: data blocks covered by one change-segment partition."""
        return self.num_blocks // self.cs_partitions

    @property
    def partition_capacity(self) -> int:
        """MDB: staged entries one change-segment partition can hold."""
        return self.log_capacity // self.cs_partitions

    @property
    def filter_words(self) -> int:
        """uint32 lanes per block's blocked-Bloom filter row (§12)."""
        return hashing_filter_words_for(self.block_entries)


def init(cfg: FlashTableConfig) -> DeviceTableState:
    if cfg.scheme == "MDB":
        log_shape = (cfg.cs_partitions, cfg.partition_capacity)
        log_ptr_shape = (cfg.cs_partitions,)
    else:
        log_shape = (cfg.log_capacity,)
        log_ptr_shape = ()
    return seg.init_state(cfg.num_blocks, cfg.block_entries,
                          log_shape, log_ptr_shape, cfg.overflow_capacity,
                          cfg.filter_words)


# ---------------------------------------------------------------------------
# MB policy (§2.3): no change segment
# ---------------------------------------------------------------------------
@jax.named_scope("mb_merge")
def _mb_update(cfg: FlashTableConfig, state: DeviceTableState, keys, cnts
               ) -> DeviceTableState:
    """MB: merge the deduped batch immediately.

    Carry (a block receiving more than ``max_updates_per_block`` updates in
    one batch) is merged again until drained, so no counts are lost."""
    state, carry_k, carry_c = seg.merge_dirty_batch(cfg, state, keys, cnts)

    def cond(t):
        return (t[1] != EMPTY).any()

    def body(t):
        st, ck, cc = t
        return seg.merge_dirty_batch(cfg, st, ck, cc)

    state, _, _ = jax.lax.while_loop(cond, body, (state, carry_k, carry_c))
    return state._replace(
        stats=state.stats._replace(merges=state.stats.merges + 1))


# ---------------------------------------------------------------------------
# MDB-L policy (§2.4): monolithic log change segment
# ---------------------------------------------------------------------------
@jax.named_scope("stage")
def _stage(cfg: FlashTableConfig, state: DeviceTableState, keys, cnts
           ) -> DeviceTableState:
    """Append a deduped chunk to the MDB-L log (sequential write).

    Merges *repeatedly* until the chunk fits behind the carried log head:
    a single forced merge may leave ``n_carry`` entries such that
    ``log_ptr + chunk`` still exceeds the capacity, and
    ``dynamic_update_slice`` would then clamp the start index and silently
    overwrite carried entries. Callers guarantee ``chunk <= log_capacity``
    (see :func:`update`), so the loop terminates: every merge shrinks the
    per-block carry by ``max_updates_per_block``.
    """
    chunk = keys.shape[0]
    cap = cfg.log_capacity
    assert chunk <= cap, "update() must split chunks larger than the log"

    state = jax.lax.while_loop(
        lambda st: st.log_ptr + chunk > cap,
        lambda st: seg.drain_log(cfg, st),
        state)
    return seg.append_log(cfg, state, keys, cnts)


# ---------------------------------------------------------------------------
# MDB policy (§2.4): partitioned change segment
# ---------------------------------------------------------------------------
def _mdb_merge_where(cfg: FlashTableConfig, state: DeviceTableState, mask
                     ) -> DeviceTableState:
    """Merge every partition whose ``mask`` entry is set."""
    def body(p, st):
        return jax.lax.cond(mask[p],
                            lambda s: seg.merge_partition(cfg, s, p),
                            lambda s: s, st)
    return jax.lax.fori_loop(0, cfg.cs_partitions, body, state)


@jax.named_scope("stage")
def _mdb_update(cfg: FlashTableConfig, state: DeviceTableState, keys, cnts
                ) -> DeviceTableState:
    """MDB: stage into per-partition buffers; a partition that cannot fit
    the incoming entries is drained first through its k-block dirty merge.

    Like the MDB-L stage path, draining loops until everything fits: a
    merge can leave carry at the partition head, so under hot-block
    pressure one drain may not make room for the whole chunk. Callers
    guarantee ``chunk <= partition_capacity`` (see :func:`update`) and
    every drain strictly shrinks a non-empty partition's staged count, so
    the loop terminates with no counts dropped."""
    P = cfg.cs_partitions
    part = seg.partition_of(cfg, keys)
    n_inc = jnp.zeros((P,), jnp.int32).at[part].add(
        (keys != EMPTY).astype(jnp.int32), mode="drop")
    state = _mdb_merge_where(
        cfg, state, state.log_ptr + n_inc > cfg.partition_capacity)
    state, rest_k, rest_c = seg.scatter_partitions(cfg, state, keys, cnts)

    def cond(t):
        return (t[1] != EMPTY).any()

    def body(t):
        st, rk, rc = t
        n_rest = jnp.zeros((P,), jnp.int32).at[seg.partition_of(cfg, rk)
                                               ].add(
            (rk != EMPTY).astype(jnp.int32), mode="drop")
        st = _mdb_merge_where(cfg, st, n_rest > 0)
        return seg.scatter_partitions(cfg, st, rk, rc)

    state, _, _ = jax.lax.while_loop(cond, body, (state, rest_k, rest_c))
    return state._replace(
        stats=state.stats._replace(stages=state.stats.stages + 1))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def _update_impl(cfg: FlashTableConfig, state: DeviceTableState, tokens,
                 deltas: Optional[jax.Array] = None) -> DeviceTableState:
    tokens = tokens.astype(jnp.int32)
    with jax.named_scope("accumulate"):
        if deltas is None:
            keys, cnts = hops.accumulate(tokens)
        else:
            keys, cnts = accumulate_deltas(tokens, deltas.astype(jnp.int32))
    if cfg.scheme == "MB":
        return _mb_update(cfg, state, keys, cnts)
    if cfg.scheme == "MDB":
        step = cfg.partition_capacity
        stage_fn = _mdb_update
    else:  # MDB-L
        step = cfg.log_capacity
        stage_fn = _stage
    # oversized chunks can never fit a (drained) change segment in one
    # piece — split them statically so staging always makes progress.
    if keys.shape[0] <= step:
        return stage_fn(cfg, state, keys, cnts)
    for i in range(0, keys.shape[0], step):
        state = stage_fn(cfg, state, keys[i:i + step], cnts[i:i + step])
    return state


#: Insert a batch of tokens (or (token, Δ) pairs) into the table.
#: ``state`` is **donated**: its buffers are updated in place (no HBM copy
#: of the table per call). Rebind the result and never reuse the argument.
update = functools.partial(jax.jit, static_argnums=0,
                           donate_argnums=1)(_update_impl)

#: Un-donated twin of :func:`update` — the pre-engine per-call discipline
#: (every call copies the table state). Kept for benchmarks that measure
#: what donation buys (``fig4dev``); new code should use :func:`update`.
update_copying = functools.partial(jax.jit, static_argnums=0)(_update_impl)


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def flush(cfg: FlashTableConfig, state: DeviceTableState) -> DeviceTableState:
    """Force a merge of any staged state (end-of-stream / checkpoint).

    Like :func:`update`, donates ``state``."""
    if cfg.scheme == "MB":
        return state
    if cfg.scheme == "MDB":
        return _mdb_merge_where(cfg, state, state.log_ptr > 0)
    return jax.lax.cond(state.log_ptr > 0,
                        lambda st: seg.drain_log(cfg, st),
                        lambda st: st, state)


@functools.partial(jax.jit, static_argnums=0)
def lookup_ex(cfg: FlashTableConfig, state: DeviceTableState, q_keys
              ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Batched point queries (paper §2.7): data segment (blocked Pallas
    probe — one tile fetch per queried block per wave) + change segment
    scan + overflow scan, each shared across the whole batch. Returns
    (counts, probe_distances, tile_loads); ``EMPTY`` entries are padding
    → ``(0, 0)``.

    With ``cfg.filters`` the blocked-Bloom pre-pass inside
    :func:`ops.query_blocked_ex` answers definite misses before any tile
    fetch — a filter-killed key reports distance 0 and contributes no
    ``tile_loads``. The filter also covers the change segment and
    overflow (staging ORs bits in too), so a filter-negative needs the
    scans only for the *surviving* keys — but the scans are batch-shared
    fixed-shape loops, so they run regardless; the engine-level short
    circuit (:mod:`query_engine`) is what skips whole dispatches.

    Read path: ``state`` is *not* donated.
    """
    q = q_keys.astype(jnp.int32)
    fw = state.filter_words if cfg.filters else None
    with jax.named_scope("query_blocked"):
        cnt, dist, tiles = hops.query_blocked_ex(
            cfg.pair, state.keys, state.counts, q, 128, fw)
    if cfg.scheme != "MB":  # MB has no change segment to consolidate
        with jax.named_scope("scan_log"):
            cnt = cnt + seg.scan_segment(state.log_keys.reshape(-1),
                                         state.log_counts.reshape(-1), q)
    with jax.named_scope("scan_overflow"):
        cnt = cnt + seg.scan_segment(state.ov_keys, state.ov_counts, q)
    return cnt, dist, tiles


def lookup(cfg: FlashTableConfig, state: DeviceTableState, q_keys
           ) -> Tuple[jax.Array, jax.Array]:
    """:func:`lookup_ex` without the tile count (compat entry)."""
    cnt, dist, _ = lookup_ex(cfg, state, q_keys)
    return cnt, dist


@functools.partial(jax.jit, static_argnums=0)
@jax.named_scope("filter_probe")
def filter_probe(cfg: FlashTableConfig, state: DeviceTableState, q_keys
                 ) -> jax.Array:
    """Engine-level may-contain verdicts (one cheap dispatch, no tiles).

    Bool ``(Q,)``: False ⇒ the key is definitively absent from the whole
    device table (data + change + overflow segments — staging and merge
    both maintain the filter), so the engine can answer 0 without
    dispatching a lookup at all. ``EMPTY`` keys test False."""
    q = q_keys.astype(jnp.int32)
    return seg.filter_may_contain(cfg.pair, state.filter_words, q)


@functools.partial(jax.jit, static_argnums=0)
def load_factor(cfg: FlashTableConfig, state: DeviceTableState) -> jax.Array:
    return (state.keys != EMPTY).mean()
