"""Compile each cell's device programs at full size for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 flashbench/compile_check.py [cell ...]

Nothing runs: the pre-load update, the store's update and flush, and the
lookup and filter programs of each cell are lowered as the run dispatches
them and compiled by the TPU compiler for a chip that is described, not
attached. A refusal (a block shape, an operation that does not lower, a
program that does not fit 16 GB) shows here, before any chip time is
spent. Prints one line per program with its bytes.
"""
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

HBM_BYTES = 16e9


def programs(cfg: dict, traffic: dict, sharding):
    """(name, jitted function, abstract arguments) of one cell."""
    import jax
    import jax.numpy as jnp
    from repro.core import table_jax as tj

    def on(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    geo = dict(q_log2=cfg["q_log2"], r_log2=cfg["r_log2"])
    mb = tj.FlashTableConfig(scheme="MB", **geo)
    own = tj.FlashTableConfig(scheme=cfg["scheme"], **geo)
    state = jax.tree.map(lambda s: on(s.shape, s.dtype),
                         jax.eval_shape(lambda: tj.init(own)))
    pre = int(cfg["preload_chunk"])
    out = [("preload_update", tj.update,
            (mb, state, on((pre,)), on((pre,))))]
    if traffic["kind"] == "ingest":
        ch = int(cfg["chunk"])
        out += [("update", tj.update, (own, state, on((ch,)), on((ch,)))),
                ("flush", tj.flush, (own, state))]
    q = int(cfg["query_chunk"])
    out += [("lookup", tj.lookup_ex, (own, state, on((q,)))),
            ("filter", tj.filter_probe, (own, state, on((q,))))]
    return out


def main(cells) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import bench
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bj = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    names = cells or [w["name"] for w in bj["workloads"]]
    for name in names:
        cell = bench.load_cell(name)
        if cell.cfg["backend"] != "device":
            print(f"{name}: {cell.cfg['backend']} backend, skipped")
            continue
        for prog, fn, args in programs(cell.cfg, cell.traffic, chip):
            c = fn.lower(*args).compile()
            m = c.memory_analysis()
            kernel = "tpu_custom_call" in c.as_text()
            total = m.argument_size_in_bytes + m.temp_size_in_bytes
            print(json.dumps({"cell": name, "program": prog,
                              "kernel": kernel,
                              "argument_bytes": m.argument_size_in_bytes,
                              "temp_bytes": m.temp_size_in_bytes,
                              "alias_bytes": m.alias_size_in_bytes}),
                  flush=True)
            if total >= HBM_BYTES:
                raise SystemExit(f"{name} {prog}: {total} bytes do not "
                                 f"fit one chip")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
