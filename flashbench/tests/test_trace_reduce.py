"""The trace reduction, on hand-made rows and on a small trace recorded
on a TPU v5e (``data/``)."""
import pytest

import trace_reduce as tr

H, D0, D1 = "/host:CPU", "/device:TPU:0", "/device:TPU:1"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE


def _rows():
    # window: group spans [0, 100] and [100, 200]; a third group is
    # outside the counted window
    return [
        (H, "python3", "group", 0, 100, ""),
        (H, "python3", "update", 0, 60, ""),
        (H, "python3", "flush", 60, 40, ""),
        (H, "python3", "group", 100, 100, ""),
        (H, "python3", "update", 100, 100, ""),
        (H, "python3", "group", 200, 50, ""),
        # device 0: module [10, 50] whose while op encloses two ops, then
        # a flush program [70, 90], then work past the window's end
        (D0, MODS, "jit__update_impl(3)", 10, 40, "jit__update_impl"),
        (D0, OPS, "while.0", 10, 40, "jit__update_impl"),
        (D0, OPS, "fusion.1", 10, 10, "jit__update_impl"),
        (D0, OPS, "sort.2", 25, 25, "jit__update_impl"),
        (D0, MODS, "jit_flush(4)", 70, 20, "jit_flush"),
        (D0, OPS, "all-to-all.5", 70, 20, "jit_flush"),
        (D0, MODS, "jit__update_impl(3)", 150, 100, "jit__update_impl"),
        (D0, OPS, "fusion.1", 150, 100, "jit__update_impl"),
        # device 1: busy [0, 200]
        (D1, MODS, "jit__update_impl(3)", 0, 200, "jit__update_impl"),
        (D1, OPS, "fusion.1", 0, 200, "jit__update_impl"),
    ]


def test_reduce_by_hand():
    red = tr.reduce_events(_rows(), "group", 2)
    ns = 1e-9
    assert red["window_s"] == pytest.approx(200 * ns)
    # device 0 busy [10, 50] + [70, 90] + [150, 200] = 110; device 1: 200
    assert red["busy_s"] == pytest.approx((110 + 200) / 2 * ns)
    assert red["programs"]["jit__update_impl"] == pytest.approx(
        (40 + 50 + 200) / 2 * ns)
    assert red["programs"]["jit_flush"] == pytest.approx(20 / 2 * ns)
    # op time goes to the leaves: the while op that encloses them is not
    # counted again
    assert red["ops"]["jit__update_impl/sort.2"] == pytest.approx(
        25 / 2 * ns)
    assert "jit__update_impl/while.0" not in red["ops"]
    assert red["collective_s"] == pytest.approx(20 / 2 * ns)
    # device 0 idle: [0,10] update; [50,70] flush (its midpoint 60 is
    # where update ends and flush starts: the later-started span wins);
    # [90,150] spans flush's end and the second update (midpoint 120)
    idle = red["idle_by_host"]
    assert idle == pytest.approx({"update": 70 * ns, "flush": 20 * ns})


def test_idle_between_calls():
    rows = [(H, "t", "query", 0, 10, ""), (H, "t", "query", 50, 10, ""),
            (D0, OPS, "fusion", 0, 10, "jit_lookup_ex"),
            (D0, OPS, "fusion", 50, 10, "jit_lookup_ex")]
    red = tr.reduce_events(rows, "query", 2)
    assert red["idle_by_host"] == pytest.approx({"between_calls": 40e-9})
    assert red["busy_s"] == pytest.approx(20e-9)


def test_breakdown_orders_and_caps():
    red = tr.reduce_events(_rows(), "group", 2)
    b = tr.breakdown(red, top=2)
    assert [k for k, _ in b["device_ops"]] == [
        "jit__update_impl/fusion.1", "jit__update_impl/sort.2"]
    assert b["device_ops"][0][1] == pytest.approx((10 + 50 + 200) / 2e9)
    assert len(b["idle_gaps"]) <= 2


def test_no_device_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events([(H, "t", "query", 0, 10, "")], "query", 1)


def _recorded():
    """Rows of a 1-second meme-mb.lookup window traced on one TPU v5e
    (13 query spans), as ``load_events`` read them from its trace."""
    import gzip
    import json
    from pathlib import Path
    path = Path(__file__).resolve().parent / "data" / "lookup_v5e_rows.json.gz"
    with gzip.open(path, "rt") as f:
        return [tuple(r) for r in json.load(f)["rows"]]


def test_recorded_trace():
    rows = _recorded()
    red = tr.reduce_events(rows, "query", 13)
    # what the run that recorded it printed (busy_s, window_s)
    assert red["window_s"] == pytest.approx(1.071594739)
    assert red["busy_s"] == pytest.approx(0.966250389)
    assert red["devices"] == 1
    # the busy union again, by a plain sweep over the op rows
    spans = sorted(r for r in rows if r[2] == "query")
    w0, w1 = spans[0][3], spans[-1][3] + spans[-1][4]
    ops = sorted((max(r[3], w0), min(r[3] + r[4], w1)) for r in rows
                 if r[1] == tr.OPS_LINE and r[3] < w1 and r[3] + r[4] > w0)
    busy, end = 0.0, w0
    for a, b in ops:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    # idle time all falls inside the query calls, and adds up
    assert set(red["idle_by_host"]) == {"query"}
    assert sum(red["idle_by_host"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    # the lookup and filter programs, and the kernels inside them
    assert set(red["programs"]) >= {"jit_lookup_ex", "jit_filter_probe"}
    assert red["programs"]["jit_lookup_ex"] <= red["busy_s"]
    names = [k for k, _ in tr.breakdown(red)["device_ops"]]
    assert names[0].startswith("jit_lookup_ex/flash_hash_query")
    assert red["collective_s"] == 0.0


def test_load_events_reads_the_benchmark_spans(tmp_path):
    """A trace taken here on the CPU: the benchmark's own host spans come
    back as rows (the CPU has no device plane to reduce)."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(4096)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in ("update", "flush", "unrelated"):
        with jax.profiler.TraceAnnotation(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    rows = tr.load_events(next(tmp_path.glob("plugins/profile/*/*.xplane.pb")))
    assert sorted(r[2] for r in rows) == ["flush", "update"]
    assert all(r[4] > 0 for r in rows)


def test_op_name():
    assert tr.op_name("%fusion.141 = s32[8388608]{0:T(1024)} fusion("
                      "s32[8388608]{0:T(1024)} %bitcast.54)") == \
        "fusion.141 s32[8388608]"
    assert tr.op_name("%while.27 = (s32[16384,1,1024]{2,1,0:T(1,128)}, "
                      "s32[]) while(%tuple.116)") == "while.27 tuple"
