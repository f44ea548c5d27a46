"""Ahead-of-time compiles of the flash-hash device path for a TPU v5e.

Nothing runs here: each test lowers a kernel (or a jitted table program)
for a *described* v5e chip and asks the TPU compiler to build it. That
catches what the Pallas interpreter cannot — block shapes Mosaic refuses,
operations it cannot lower, programs that do not fit the device — at
the paper's Wiki geometry (``q_log2=24``, ``r_log2=10``: 16,384 blocks of
1,024 slots) with the defaults the store runs (``max_u=512``,
``qcap=128``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import table_jax as tj
from repro.core.hashing import Pow2Hash, filter_words_for
from repro.kernels.flash_hash import kernel

Q_LOG2, R_LOG2, MAX_U, QCAP = 24, 10, 512, 128
N_B, R = 1 << (Q_LOG2 - R_LOG2), 1 << R_LOG2
FW = filter_words_for(R)
HBM_BYTES = 16 * 2**30          # one v5e chip
PAIR = Pow2Hash(q_log2=Q_LOG2, r_log2=R_LOG2)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's compiles cannot be read back from the persistent
    # cache without the chip, so keep them out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled(fn, *args):
    c = fn.lower(*args).compile()
    assert "tpu_custom_call" in c.as_text()   # the kernel, not interpreted
    return c


def _tables(s):
    return (_on(s, (N_B, 1, R)), _on(s, (N_B, 1, R)),
            _on(s, (N_B, 1, FW), jnp.uint32))


def test_merge_compiles(one_chip):
    _compiled(kernel.merge, PAIR, *_tables(one_chip),
              _on(one_chip, (N_B, MAX_U)), _on(one_chip, (N_B, MAX_U)))


def test_merge_dirty_compiles(one_chip):
    _compiled(kernel.merge_dirty, PAIR, *_tables(one_chip),
              _on(one_chip, (N_B,)),
              _on(one_chip, (N_B, MAX_U)), _on(one_chip, (N_B, MAX_U)))


def test_query_grid_compiles(one_chip):
    keys, counts, _ = _tables(one_chip)
    _compiled(kernel.query_grid, PAIR, keys, counts,
              _on(one_chip, (1024,)), _on(one_chip, (1024, QCAP)))


def test_filter_probe_grid_compiles(one_chip):
    *_, filt = _tables(one_chip)
    _compiled(kernel.filter_probe_grid, filt,
              _on(one_chip, (1024,)), _on(one_chip, (1024, QCAP)))


def _state(cfg, sharding):
    return jax.tree.map(lambda s: _on(sharding, s.shape, s.dtype),
                        jax.eval_shape(lambda: tj.init(cfg)))


@pytest.fixture(scope="module")
def mdbl():
    return tj.FlashTableConfig(q_log2=Q_LOG2, r_log2=R_LOG2, scheme="MDB-L")


def test_update_program_compiles_and_fits(one_chip, mdbl):
    c = _compiled(tj.update, mdbl, _state(mdbl, one_chip),
                  _on(one_chip, (4096,)), _on(one_chip, (4096,)))
    m = c.memory_analysis()
    # donated state: the table is updated in place, not copied
    assert m.alias_size_in_bytes >= 2 * N_B * R * 4
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < HBM_BYTES


def test_lookup_program_compiles(one_chip, mdbl):
    _compiled(tj.lookup_ex, mdbl, _state(mdbl, one_chip),
              _on(one_chip, (1024,)))


def test_sharded_programs_compile_for_four_chips(topo, one_chip):
    """The sharded store's update and lookup programs over the 2x2 mesh:
    4 shards of ``q_log2=23``, the ``all_to_all`` routing updates to
    their owner shard and the kernels compiled on every shard."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import distributed as D
    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("table",))
    n = mesh.size
    cfg = D.ShardedTableConfig(
        local=tj.FlashTableConfig(q_log2=23, r_log2=R_LOG2, scheme="MDB-L"),
        num_shards=n)
    rows = NamedSharding(mesh, P("table"))
    state = jax.tree.map(lambda s: _on(rows, s.shape, s.dtype),
                         jax.eval_shape(lambda: D.init_global(cfg)))
    toks = _on(rows, (n * 1024,))
    upd = _compiled(D.make_update_fn(cfg, mesh, "table", with_deltas=True,
                                     donate=True), state, toks, toks)
    assert "all-to-all" in upd.as_text()
    q = _on(NamedSharding(mesh, P()), (1024,))
    _compiled(D.make_lookup_fn(cfg, mesh, "table", with_dist=True,
                               with_tiles=True), state, q)
