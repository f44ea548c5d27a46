"""The program's spans and scopes read from a trace: on hand-made rows,
on the recorded lookup trace (which holds none of them), on a hand-built
protobuf, and on a tiny store traced on a TPU v5e (``data/``)."""
import gzip
import json
from pathlib import Path

import pytest

import program_trace as pt
import trace_reduce as tr
from test_trace_reduce import _recorded, _rows

H, D0 = "/host:CPU", "/device:TPU:0"
OPS, MODS = tr.OPS_LINE, tr.MODULES_LINE
OLD_KEYS = ("window_s", "busy_s", "devices", "programs", "ops",
            "collective_s", "idle_by_host")


def _program_rows():
    """One update call [0, 100] on the caller's line (index 7) while the
    drain worker (line 6, the same thread name) runs a job; the device is
    busy [0, 10], [30, 40], [70, 100]."""
    return [
        (H, "python3", "group", 0, 100, "", "7"),
        (H, "python3", "update", 0, 100, "", "7"),
        (H, "python3", "flashstore.update", 0, 100, "", "7"),
        (H, "python3", "flashstore.write.dedup", 5, 20, "", "7"),
        (H, "python3", "flashstore.write.fold", 40, 20, "", "7"),
        (H, "python3", "flashstore.drain.job", 45, 30, "", "6"),
        (H, "python3", "flashstore.drain.dispatch", 50, 10, "", "6"),
        (D0, MODS, "jit__update_impl(9)", 0, 100, "jit__update_impl", ""),
        (D0, OPS, "while.1", 0, 10, "jit__update_impl",
         "jit(_update_impl)/stage/while"),
        (D0, OPS, "fusion.2", 0, 10, "jit__update_impl",
         "jit(_update_impl)/stage/while/body/drain_log/append_overflow/"
         "scatter_rows/jit(argsort)/sort"),
        (D0, OPS, "fusion.3", 30, 10, "jit__update_impl",
         "jit(_update_impl)/accumulate/jit(accumulate)/sort"),
        (D0, OPS, "fusion.4", 70, 30, "jit__update_impl", ""),
    ]


def test_old_keys_are_trace_reduce_s():
    for rows, span, n in [(_rows(), "group", 2), (_program_rows(), "group", 1),
                          (_recorded(), "query", 13)]:
        red = pt.reduce_events(rows, span, n)
        old = tr.reduce_events([r[:6] for r in rows], span, n)
        assert {k: red[k] for k in OLD_KEYS} == old


def test_self_time_with_nested_spans():
    red = pt.reduce_events(_program_rows(), "group", 1)
    ns = 1e-9
    assert red["spans"] == pytest.approx({
        "flashstore.update": 60 * ns,       # 100 less its two children
        "flashstore.write.dedup": 20 * ns,
        "flashstore.write.fold": 20 * ns,
        # the worker's span on the other line takes nothing from update
        "flashstore.drain.job": 20 * ns,
        "flashstore.drain.dispatch": 10 * ns})


def test_idle_by_span_across_two_lines_of_one_name():
    red = pt.reduce_events(_program_rows(), "group", 1)
    ns = 1e-9
    # gaps [10, 30] (mid 20: dedup), [40, 70] (mid 55: fold, while the
    # worker dispatches)
    assert red["idle_by_span"] == pytest.approx({
        "update/flashstore.write.dedup": 20 * ns,
        "update/flashstore.write.fold+drain:flashstore.drain.dispatch":
            30 * ns})
    assert red["idle_by_host"] == pytest.approx({"update": 50 * ns})
    # the old label is the prefix of each new one, and they add up
    assert all(k.startswith("update") for k in red["idle_by_span"])
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        sum(red["idle_by_host"].values()))


def test_drain_label_alone_and_bare_labels():
    rows = [(H, "t", "flush", 0, 100, "", "0"),
            (H, "t", "flashstore.drain.job", 20, 40, "", "1"),
            (H, "t", "flashstore.drain.device_wait", 30, 20, "", "1"),
            (D0, OPS, "fusion", 0, 10, "jit_flush", ""),
            (D0, OPS, "fusion", 90, 10, "jit_flush", "")]
    red = pt.reduce_events(rows, "flush", 1)
    assert red["idle_by_span"] == pytest.approx(
        {"flush+drain:flashstore.drain.device_wait": 80e-9})
    assert pt.caller_span("flush+drain:flashstore.drain.device_wait") == ""
    rows = [(H, "t", "query", 0, 10, "", "0"),
            (H, "t", "query", 50, 10, "", "0"),
            (D0, OPS, "fusion", 0, 10, "jit_lookup_ex", ""),
            (D0, OPS, "fusion", 50, 10, "jit_lookup_ex", "")]
    red = pt.reduce_events(rows, "query", 2)
    assert red["idle_by_span"] == pytest.approx({"between_calls": 40e-9})


def test_scope_sums_and_breakdown():
    red = pt.reduce_events(_program_rows(), "group", 1)
    ns = 1e-9
    # leaf ops only: the while op encloses fusion.2
    assert red["scopes"] == pytest.approx({"scatter_rows": 10 * ns,
                                           "accumulate": 10 * ns})
    names = [k for k, _ in pt.breakdown(red)["device_ops"]]
    assert names == ["jit__update_impl/fusion.4",
                     "jit__update_impl/fusion.2 [scatter_rows]",
                     "jit__update_impl/fusion.3 [accumulate]"]
    got = pt.shares(red)
    # staging: 10 of the update program's 100 ns
    assert got["table.staging_share"] == pytest.approx(10.0)
    assert got["write.host_share"] == pytest.approx(40.0)
    assert got["query.idle_share.host"] is None


@pytest.mark.parametrize("path,scope", [
    ("jit(_update_impl)/stage/while/body/drain_log/bucket_rows/"
     "jit(_where)/select_n", "bucket_rows"),
    ("jit(lookup_ex)/query_blocked/jit(query_blocked_ex)/query_waves/while/"
     "body/jit(query_grid)/cond/branch_0_fun/flash_hash_query/pallas_call",
     "flash_hash_query"),
    ("jit(_update_impl)/stage/while", "stage"),
    ("jit(flush)/cond/branch_1_fun/vmap()/add", ""),
    ("", ""),
])
def test_scope_of(path, scope):
    assert pt.scope_of(path) == scope


def _msg(*fields):
    """A protobuf message from ``(field, value)``: ints as varints,
    bytes and str as length-delimited fields."""
    def varint(n):
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | (0x80 if n else 0))
            if not n:
                return bytes(out)
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def test_op_paths_read_from_the_wire(tmp_path):
    stat_md = [_msg((1, i), (2, _msg((1, i), (2, n))))
               for i, n in [(1, "program_id"), (2, "tf_op"), (3, "flops"),
                            (4, "jit(f)/scope_b/add:")]]

    def event(i, text, pid, op):
        op_stat = (_msg((1, 2), (5, op)) if op.startswith("jit(f)/scope_a")
                   else _msg((1, 2), (7, 4)))   # interned string
        md = _msg((1, i), (2, text), (5, _msg((1, 1), (3, pid))),
                  (5, op_stat), (5, _msg((1, 3), (3, 77))))
        return _msg((1, i), (2, md))

    plane = _msg((2, D0), (3, _msg((2, "XLA Ops"))),
                 (4, event(1, "%fusion.1 = s32[4] fusion()", 11,
                           "jit(f)/scope_a/mul:")),
                 (4, event(2, "%fusion.2 = s32[4] fusion()", 12, "")),
                 *[(5, m) for m in stat_md])
    host = _msg((2, "/host:CPU"), (4, event(9, "x", 1, "jit(f)/scope_a/y:")))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, plane), (1, host)))
    got = pt.op_paths(path)
    assert set(got) == {D0}
    assert got[D0] == {
        (11, "%fusion.1 = s32[4] fusion()"): "jit(f)/scope_a/mul",
        (None, "%fusion.1 = s32[4] fusion()"): "jit(f)/scope_a/mul",
        (12, "%fusion.2 = s32[4] fusion()"): "jit(f)/scope_b/add",
        (None, "%fusion.2 = s32[4] fusion()"): "jit(f)/scope_b/add"}


def test_load_events_reads_program_spans(tmp_path):
    """A device store traced here on the CPU: its program spans come back
    as host rows, the worker's on a line of its own (no device plane)."""
    import jax
    import numpy as np

    from repro.core import FlashStore
    store = FlashStore.open(backend="device", scheme="MDB-L", q_log2=10,
                            r_log2=6, log_capacity=1 << 9, chunk=128)
    keys = np.arange(1, 100, dtype=np.int64)
    store.update(keys)
    store.flush(wait=True)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("update"):
        store.update(keys)
    with jax.profiler.TraceAnnotation("flush"):
        store.flush(wait=True)
    jax.profiler.stop_trace()
    store.close()
    rows = pt.load_events(next(tmp_path.glob("plugins/profile/*/*.xplane.pb")))
    assert {r[2] for r in rows} >= {
        "update", "flush", "flashstore.update", "flashstore.flush",
        "flashstore.write.dedup", "flashstore.drain.job",
        "flashstore.drain.dispatch", "flashstore.drain.merge"}
    line = {r[2]: r[6] for r in rows}
    assert line["flashstore.update"] == line["update"]
    assert line["flashstore.drain.job"] != line["flashstore.update"]


FIXTURES = Path(__file__).resolve().parent / "data"


def _fixture(name):
    """Rows of a traced window of a tiny cell (``tiny.py``'s sizes) on one
    TPU v5e, kept with ``program_trace.py --keep``, read by
    :func:`program_trace.load_events` and cut to the rows that overlap its
    first three groups (or batches): the reduction is the same on both."""
    with gzip.open(FIXTURES / name, "rt") as f:
        data = json.load(f)
    return [tuple(r) for r in data["rows"]], data["window"]


@pytest.mark.parametrize("name", ["program_v5e_ingest_rows.json.gz",
                                  "program_v5e_lookup_rows.json.gz"])
def test_recorded_program_trace(name):
    rows, (span, count) = _fixture(name)
    red = pt.reduce_events(rows, span, count)
    old = tr.reduce_events([r[:6] for r in rows], span, count)
    assert {k: red[k] for k in OLD_KEYS} == old
    # named scopes cover the programs' leaf time
    programs = ("jit__update_impl", "jit_flush") if span == "group" else (
        "jit_lookup_ex", "jit_filter_probe")
    leaf = sum(v for k, v in red["ops"].items()
               if k.split("/")[0] in programs)
    scoped = sum(v for k, v in red["ops"].items()
                 if k.split("/")[0] in programs and k in red["op_scopes"])
    assert scoped >= 0.9 * leaf
    # every idle gap is charged once, under its old label as a prefix
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        sum(red["idle_by_host"].values()))
    for label in red["idle_by_host"]:
        assert sum(v for k, v in red["idle_by_span"].items()
                   if k.split("/")[0].split("+")[0] == label) == \
            pytest.approx(red["idle_by_host"][label])
    assert all(v >= 0 for v in red["spans"].values())
    got = pt.shares(red)
    if span == "group":
        assert any("+drain:" in k for k in red["idle_by_span"])
        assert {"flashstore.update", "flashstore.drain.job"} <= set(
            red["spans"])
        assert 0 < got["table.staging_share"] < 100
        assert 0 < got["write.host_share"] < 100
        assert got["query.idle_share.host"] is None
    else:
        assert {"flashstore.query", "flashstore.query.lookup"} <= set(
            red["spans"])
        total = 100 * (1 - red["busy_s"] / red["window_s"])
        assert (got["query.idle_share.host"] + got["query.idle_share.sync"]
                + got["idle_share.bare"]) == pytest.approx(total)
        assert got["table.staging_share"] is None
