"""Distributed flash-hash table: the paper's design scaled across chips.

The data segment is sharded over a mesh axis by *block id* — the two-level
hash gives the owner mapping for free:

    owner(x) = s(x) >> log2(blocks_per_shard)

Each device runs the single-device policy (``table_jax``) over its local
blocks. A distributed update is: local RAM-buffer dedup → bucket staged
entries by owner shard → one ``all_to_all`` → local stage/merge. This is
the cross-chip version of the paper's "batch updates per block": the
*only* inter-chip traffic is one fixed-size collective per flush, and all
writes land block-local on the owner (semi-random discipline end-to-end).

Fixed-capacity buckets (``bucket_cap`` entries per destination shard) keep
the collective statically shaped; overflowing entries are carried over to
the next flush (same deferred-update discipline as the tile merge).

Async-safe drains (DESIGN.md §9): the update/flush programs built with
``donate=True`` donate the global state, and the sharded store runs them
on its background drain worker. Per-shard drains therefore follow the
same off-thread discipline as the single table — exactly one drain in
flight, the worker is the only caller of the donated programs, and every
dispatch is guarded by :func:`assert_live` (re-exported from
:mod:`segments`) so a raced state fails loudly.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import table_jax as tj
from .hashing import Pow2Hash

EMPTY = tj.EMPTY
assert_live = tj.assert_live    # off-thread donation guard (DESIGN.md §9)


@dataclasses.dataclass(frozen=True)
class ShardedTableConfig:
    local: tj.FlashTableConfig = dataclasses.field(
        default_factory=tj.FlashTableConfig)
    num_shards: int = 1
    bucket_cap: int = 1 << 12     # entries per (src, dst) bucket per flush

    @property
    def global_blocks(self) -> int:
        return self.local.num_blocks * self.num_shards

    @property
    def global_pair(self) -> Pow2Hash:
        c = self.local
        shard_log2 = (self.num_shards - 1).bit_length()
        return Pow2Hash(q_log2=c.q_log2 + shard_log2, r_log2=c.r_log2)


def init_global(cfg: ShardedTableConfig) -> tj.DeviceTableState:
    """Global-view state: leaves have a leading per-shard dim stacked, i.e.
    keys (num_shards * n_b_local, r); shard over a mesh axis with
    :func:`state_pspec`."""
    local = tj.init(cfg.local)

    def rep(x):
        return jnp.tile(x[None], (cfg.num_shards,) + (1,) * x.ndim).reshape(
            (cfg.num_shards * x.shape[0],) + x.shape[1:]) if x.ndim else \
            jnp.tile(x[None], (cfg.num_shards,))

    return jax.tree.map(rep, local)


def state_pspec(axis: str,
                local: tj.FlashTableConfig | None = None
                ) -> tj.DeviceTableState:
    """PartitionSpec pytree for the global state (all leaves sharded on
    their leading, per-shard dim). The tree structure is scheme-independent
    (MDB's ``(cs_partitions,)`` log pointers tile to ``(n * cs_partitions,)``
    and shard on the same leading dim), so ``local`` is only needed when the
    default config would not build — it never changes the specs."""
    return jax.tree.map(lambda _: P(axis),
                        tj.init(local or tj.FlashTableConfig()))


def _bucket_by_owner(cfg: ShardedTableConfig, keys, cnts):
    """Pack deduped updates into (num_shards, bucket_cap) owner buckets."""
    n = cfg.num_shards
    cap = cfg.bucket_cap
    pair = cfg.global_pair
    blocks_per_shard_log2 = cfg.local.q_log2 - cfg.local.r_log2
    valid = keys != EMPTY
    owner = jnp.where(valid,
                      pair.s(keys) >> blocks_per_shard_log2, n)
    order = jnp.argsort(owner, stable=True)
    sk, sc, so = keys[order], cnts[order], owner[order]
    start = jnp.searchsorted(so, jnp.arange(n + 1, dtype=so.dtype))
    pos = jnp.arange(keys.shape[0], dtype=jnp.int32) - start[jnp.clip(so, 0, n)]
    keep = (so < n) & (pos < cap)
    row = jnp.where(keep, so, n)
    buk = jnp.full((n, cap), EMPTY, jnp.int32).at[
        row, jnp.where(keep, pos, 0)].set(sk, mode="drop")
    buc = jnp.zeros((n, cap), jnp.int32).at[
        row, jnp.where(keep, pos, 0)].set(sc, mode="drop")
    dropped = ((so < n) & ~keep)
    carry_k = jnp.where(dropped, sk, EMPTY)
    carry_c = jnp.where(dropped, sc, 0)
    return buk, buc, carry_k, carry_c


def _squeeze(state, local: tj.FlashTableConfig | None = None):
    """Drop the leading per-shard dim of scalar leaves inside shard_map.

    Scheme-aware (ISSUE 10): MB / MDB-L keep a scalar ``log_ptr`` (tiled to
    ``(n,)`` globally, ``(1,)`` per shard — squeeze to ``()``); MDB keeps a
    *vector* of per-change-segment-partition pointers (``(cs_partitions,)``
    locally, tiled to ``(n * cs_partitions,)`` globally) that arrives inside
    shard_map already in its local shape and must not be squeezed."""
    scalar_log = local is None or local.scheme != "MDB"
    return state._replace(
        log_ptr=(state.log_ptr.reshape(state.log_ptr.shape[1:])
                 if scalar_log else state.log_ptr),
        ov_ptr=state.ov_ptr.reshape(()),
        stats=jax.tree.map(lambda x: x.reshape(()), state.stats))


def _expand(state, local: tj.FlashTableConfig | None = None):
    """Restore the leading per-shard dim on scalar leaves for out_specs.
    Inverse of :func:`_squeeze` — MDB's ``(cs_partitions,)`` log pointers
    already carry their sharded leading dim and pass through untouched."""
    scalar_log = local is None or local.scheme != "MDB"
    return state._replace(
        log_ptr=(state.log_ptr.reshape((1,) + state.log_ptr.shape)
                 if scalar_log else state.log_ptr),
        ov_ptr=state.ov_ptr.reshape((1,)),
        stats=jax.tree.map(lambda x: x.reshape((1,)), state.stats))


def make_update_fn(cfg: ShardedTableConfig, mesh, axis: str,
                   with_deltas: bool = False, donate: bool = False):
    """Build a shard_map'd update: ``(state, tokens) -> (state, n_carried)``
    (or ``(state, tokens, deltas) -> ...`` with ``with_deltas``).

    ``tokens`` is sharded over ``axis`` (each shard contributes its local
    stream); state is block-sharded over the same axis. ``with_deltas``
    switches the in-kernel RAM-buffer dedup to the ±Δ variant
    (:func:`segments.accumulate_deltas`) so decrements/cancellation reach
    the sharded table too. ``donate=True`` donates the state argument —
    the engine discipline (DESIGN.md §7): buffers update in place, the
    caller rebinds and never reuses the donated value.
    """
    from ..kernels.flash_hash import ops as hops
    local_cfg = cfg.local
    spec = state_pspec(axis, local_cfg)

    def local_update(state: tj.DeviceTableState, tokens, deltas=None):
        state = _squeeze(state, local_cfg)
        if deltas is None:
            keys, cnts = hops.accumulate(tokens.astype(jnp.int32))
        else:
            keys, cnts = tj.accumulate_deltas(tokens.astype(jnp.int32),
                                              deltas.astype(jnp.int32))
        buk, buc, carry_k, carry_c = _bucket_by_owner(cfg, keys, cnts)
        # one collective per flush: (n_shards, cap) -> (n_shards, cap)
        buk = jax.lax.all_to_all(buk, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        buc = jax.lax.all_to_all(buc, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        got_k = buk.reshape(-1)
        got_c = buc.reshape(-1)
        # Key coordinates need no translation: with power-of-two geometry
        # and a shared multiplier, g_local(x) == g_global(x) & (q_local-1),
        # so local block = global block & (n_b_local-1) and the home-within-
        # block bits are identical — owner routing and local placement agree
        # by construction (placement property, sharded edition).
        state = tj.update(local_cfg, state, got_k, got_c)
        # replicated scalar (psum over shards) rather than a per-shard
        # vector: in a multi-process mesh only replicated outputs are
        # addressable from every host, and the stores only ever consumed
        # the sum anyway.
        n_carry = jax.lax.psum(
            (carry_k != EMPTY).sum(dtype=jnp.int32), axis)
        return _expand(state, local_cfg), n_carry

    if with_deltas:
        body = local_update
        in_specs = (spec, P(axis), P(axis))
    else:
        body = lambda state, tokens: local_update(state, tokens)
        in_specs = (spec, P(axis))
    upd = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                        out_specs=(spec, P()),
                        check_vma=False)
    return jax.jit(upd, donate_argnums=(0,) if donate else ())


def make_lookup_fn(cfg: ShardedTableConfig, mesh, axis: str,
                   with_dist: bool = False, with_tiles: bool = False):
    """Build a shard_map'd lookup: every shard queries the full batch
    against its local blocks; non-owned keys contribute 0; one psum
    combines. (Read path = the paper's fast random reads.)

    ``with_dist=True`` additionally returns the per-key probe distance
    (the owner shard's device probe; non-owners contribute 0), matching
    the ``(counts, distances)`` contract of :func:`table_jax.lookup` so a
    :class:`~.query_engine.BatchedQueryEngine` can front this path.
    ``with_tiles=True`` (requires ``with_dist``) appends the tile-load
    count summed over shards as a replicated scalar — the engine adds it
    to its ``tile_loads`` counter. (Replicated, not ``(n_shards,)``: a
    multi-process mesh can only read replicated outputs locally.)
    """
    local_cfg = cfg.local
    spec = state_pspec(axis, local_cfg)

    def local_lookup(state: tj.DeviceTableState, q):
        state = _squeeze(state, local_cfg)
        blocks_per_shard_log2 = cfg.local.q_log2 - cfg.local.r_log2
        owner = cfg.global_pair.s(q) >> blocks_per_shard_log2
        me = jax.lax.axis_index(axis)
        mine = owner == me
        masked_q = jnp.where(mine, q, EMPTY)
        cnt, dist, tiles = tj.lookup_ex(local_cfg, state, masked_q)
        cnt = jax.lax.psum(jnp.where(mine, cnt, 0), axis)
        if not with_dist:
            return cnt
        dist = jax.lax.psum(jnp.where(mine, dist, 0), axis)
        if not with_tiles:
            return cnt, dist
        return cnt, dist, jax.lax.psum(tiles, axis)

    if with_tiles and not with_dist:
        raise ValueError("with_tiles requires with_dist")
    out_specs = (P() if not with_dist
                 else (P(), P(), P()) if with_tiles
                 else (P(), P()))
    look = jax.shard_map(local_lookup, mesh=mesh,
                         in_specs=(spec, P()),
                         out_specs=out_specs,
                         check_vma=False)
    return jax.jit(look)


def make_filter_fn(cfg: ShardedTableConfig, mesh, axis: str):
    """Build a shard_map'd Bloom pre-filter (DESIGN.md §12): every shard
    tests the full batch against its local per-block filters; non-owned
    keys contribute 0; one psum combines. Returns an int32 may-contain
    mask (0 ⇒ definitively absent from every shard) with the
    ``(state, keys) -> mask`` contract the query engine's ``filter_fn``
    expects."""
    local_cfg = cfg.local
    spec = state_pspec(axis, local_cfg)

    def local_filter(state: tj.DeviceTableState, q):
        state = _squeeze(state, local_cfg)
        blocks_per_shard_log2 = cfg.local.q_log2 - cfg.local.r_log2
        owner = cfg.global_pair.s(q) >> blocks_per_shard_log2
        me = jax.lax.axis_index(axis)
        mine = owner == me
        masked_q = jnp.where(mine, q, EMPTY)
        may = tj.filter_probe(local_cfg, state, masked_q)
        return jax.lax.psum(
            jnp.where(mine, may, False).astype(jnp.int32), axis)

    filt = jax.shard_map(local_filter, mesh=mesh,
                         in_specs=(spec, P()),
                         out_specs=P(),
                         check_vma=False)
    return jax.jit(filt)


def make_flush_fn(cfg: ShardedTableConfig, mesh, axis: str,
                  donate: bool = False):
    """Build a shard_map'd device merge: every shard drains its staged
    change segment through :func:`table_jax.flush` (end-of-stream /
    checkpoint). No collective — merges are block-local by construction."""
    local_cfg = cfg.local
    spec = state_pspec(axis, local_cfg)

    def local_flush(state: tj.DeviceTableState):
        return _expand(tj.flush(local_cfg, _squeeze(state, local_cfg)),
                       local_cfg)

    fl = jax.shard_map(local_flush, mesh=mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
    return jax.jit(fl, donate_argnums=(0,) if donate else ())


# ---------------------------------------------------------------------------
# Multi-process (multi-host) helpers — ISSUE 10.
#
# Everything above is process-count agnostic: the programs are plain
# shard_map'd jits over a mesh. What changes on a multi-process mesh
# (``jax.distributed.initialize``) is *array placement*: a process can only
# materialise its addressable shards, so global inputs are built with
# ``jax.make_array_from_callback`` instead of ``device_put``/implicit
# commitment, and anything a host needs to *read back* must come out
# replicated (``P()``), which is why ``n_carry`` and the tile-load counter
# above are psums. The helpers below are also correct on a single-process
# mesh — the sharded store uses them unconditionally in multihost mode and
# the tests reuse them in-process.
# ---------------------------------------------------------------------------


def host_shards(mesh, axis: str) -> list[int]:
    """Mesh positions (== shard ids) owned by the calling process.

    With ``jax.make_mesh((n,), (axis,))`` over id-ordered devices the
    shards of process *p* are contiguous, but we derive ownership from the
    mesh itself rather than assume it."""
    me = jax.process_index()
    return [i for i, d in enumerate(mesh.devices.reshape(-1))
            if d.process_index == me]


def place_global(cfg: ShardedTableConfig, mesh, axis: str
                 ) -> tj.DeviceTableState:
    """:func:`init_global` for multi-process meshes: every process builds
    the (identical, deterministic) host-side global init and materialises
    only its addressable shards via ``jax.make_array_from_callback``."""
    import numpy as np
    from jax.sharding import NamedSharding
    local = jax.tree.map(np.asarray, tj.init(cfg.local))
    sh = NamedSharding(mesh, P(axis))

    def place(x):
        if x.ndim:
            g = np.tile(x[None], (cfg.num_shards,) + (1,) * x.ndim).reshape(
                (cfg.num_shards * x.shape[0],) + x.shape[1:])
        else:
            g = np.tile(x[None], (cfg.num_shards,))
        return jax.make_array_from_callback(
            g.shape, sh, lambda idx, g=g: g[idx])

    return jax.tree.map(place, local)


def make_global_batch(mesh, axis: str, arr) -> jax.Array:
    """Place a host-side array as a global array sharded over ``axis``.
    ``arr`` must be the *global* value (identical shape on every process);
    each process materialises only its addressable slices."""
    import numpy as np
    from jax.sharding import NamedSharding
    a = np.asarray(arr)
    sh = NamedSharding(mesh, P(axis))
    return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])


def make_replicated(mesh, arr) -> jax.Array:
    """Place a host-side array fully replicated over ``mesh`` (for query
    batches: the read path takes the full batch on every shard). The value
    must be identical on every process — collective calls are SPMD."""
    import numpy as np
    from jax.sharding import NamedSharding
    a = np.asarray(arr)
    sh = NamedSharding(mesh, P())
    return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])


def make_sync_fn(cfg: ShardedTableConfig, mesh, axis: str, width: int = 2):
    """Build the drain-agreement collective: ``(n_shards, width)`` int32 in
    (each process fills its own shards' rows), element-wise max over shards
    out, replicated. The multihost store runs it on the *caller* thread
    (post-settle, pre-submit) so hosts agree on the number of drain waves —
    and on whether a device merge is needed — before the worker launches
    any collective program; the global collective order stays
    ``agree_k < waves_k < agree_{k+1}`` on every host (DESIGN.md §14)."""

    def local_max(v):  # v: (1, width) per shard
        return jax.lax.pmax(v.reshape(v.shape[1:]), axis)

    sync = jax.shard_map(local_max, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(), check_vma=False)
    return jax.jit(sync)
