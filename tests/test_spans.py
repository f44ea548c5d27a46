"""The store's host spans and device scopes (DESIGN.md §15).

A profiler trace of a device store holds the ``flashstore.*`` spans
nested as the store opens them, the drain worker's on a host line of
its own; outside a trace a span records nothing and raises nothing;
and every jitted program names its steps with ``jax.named_scope``, so
the ``op_name`` metadata of each operation carries the step it belongs
to."""
import re
import sys

import numpy as np
import pytest

from repro.core import spans
from repro.core import table_jax as tj
from repro.core.store import FlashStore


def _cfg(scheme, **kw):
    base = dict(q_log2=10, r_log2=6, scheme=scheme, log_capacity=1 << 9,
                cs_partitions=4, max_updates_per_block=1 << 6,
                overflow_capacity=1 << 9)
    base.update(kw)
    return tj.FlashTableConfig(**base)


def _host_spans(trace_dir):
    """``[(line index, name, start_ns, end_ns)]`` of every
    ``flashstore.*`` event on the host plane of the trace."""
    from jax.profiler import ProfileData
    path = next(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for li, line in enumerate(plane.lines):
            out += [(li, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith(spans.PREFIX)]
    return out


def _inside(child, parents):
    """Each ``child`` event sits within some ``parents`` event on its
    own host line."""
    return all(any(p[0] == c[0] and p[2] <= c[2] and c[3] <= p[3]
                   for p in parents) for c in child)


def test_spans_nest_as_the_store_opens_them(tmp_path):
    import jax
    rng = np.random.default_rng(3)
    present = rng.integers(1, 1 << 20, 300, dtype=np.int64)
    absent = present + (1 << 21)
    store = FlashStore.open(backend="device", scheme="MDB-L",
                            q_log2=10, r_log2=6, log_capacity=1 << 9,
                            chunk=256, query_chunk=128)
    # 300 keys stay under the auto-flush threshold (2 chunks): the seal
    # is the flush's
    store.update(present)                    # compile outside the trace
    store.flush(wait=True)
    store.query(np.concatenate([present, absent]))
    jax.profiler.start_trace(str(tmp_path))
    store.update(present)
    store.flush(wait=True)
    got = store.query(np.concatenate([present, absent]))
    jax.profiler.stop_trace()
    assert (got[:present.size] == 2).all() and (got[present.size:] == 0).all()
    store.close()

    ev = _host_spans(tmp_path)
    by = {}
    for e in ev:
        by.setdefault(e[1], []).append(e)
    need = ["update", "flush", "query", "write.dedup", "write.fold",
            "write.seal", "write.wait", "drain.job", "drain.dispatch",
            "drain.merge", "drain.device_wait", "query.lock", "query.dedup",
            "query.filter", "query.lookup", "query.remember",
            "query.overlay"]
    assert set(spans.PREFIX + n for n in need) <= set(by), sorted(by)

    def s(name):
        return by[spans.PREFIX + name]

    for child, parent in [
            ("write.dedup", "update"), ("write.fold", "update"),
            ("write.seal", "flush"), ("write.wait", "flush"),
            ("drain.dispatch", "drain.job"), ("drain.merge", "drain.job"),
            ("drain.device_wait", "drain.job"),
            ("query.lock", "query"), ("query.dedup", "query"),
            ("query.filter", "query"), ("query.lookup", "query"),
            ("query.remember", "query"), ("query.overlay", "query")]:
        assert _inside(s(child), s(parent)), (child, parent)
    # the drain worker's spans are on a host line of their own: the same
    # thread name can label both lines, the index tells them apart
    caller = {e[0] for e in s("update") + s("flush") + s("query")}
    worker = {e[0] for e in s("drain.job")}
    assert len(caller) == 1 and len(worker) == 1 and caller != worker


def test_synchronous_store_runs_drain_spans_on_the_caller(tmp_path):
    import jax
    store = FlashStore.open(backend="device", scheme="MB", q_log2=10,
                            r_log2=6, chunk=128, async_flush=False)
    keys = np.arange(1, 200, dtype=np.int64)
    store.update(keys)
    store.flush(wait=True)
    jax.profiler.start_trace(str(tmp_path))
    store.update(keys)
    store.flush(wait=True)
    jax.profiler.stop_trace()
    assert (store.query(keys) == 2).all()
    store.close()
    ev = _host_spans(tmp_path)
    names = {e[1] for e in ev}
    assert {"flashstore.drain.job", "flashstore.drain.dispatch"} <= names
    assert len({e[0] for e in ev}) == 1      # one thread did it all
    jobs = [e for e in ev if e[1] == "flashstore.drain.job"]
    assert _inside(jobs, [e for e in ev if e[1] == "flashstore.flush"])


def test_span_outside_a_trace_records_nothing(tmp_path, monkeypatch):
    import jax
    with spans.span("outside"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    with spans.span("inside"):
        pass
    jax.profiler.stop_trace()
    with spans.span("after"):
        pass
    names = {e[1] for e in _host_spans(tmp_path)}
    assert names == {"flashstore.inside"}
    # a process that never imported JAX cannot be tracing: a null context
    monkeypatch.delitem(sys.modules, "jax.profiler")
    with spans.span("no jax") as got:
        assert got is None


def _op_path_parts(lowered) -> set:
    text = lowered.as_text(dialect="hlo", debug_info=True)
    return {p for name in re.findall(r'op_name="([^"]*)"', text)
            for p in name.split("/")}


@pytest.mark.parametrize("scheme,scopes", [
    ("MDB-L", {"accumulate", "stage", "append_log", "filter_or",
               "drain_log", "dirty_perm", "bucket_rows", "merge_dirty",
               "append_overflow"}),
    ("MB", {"accumulate", "mb_merge", "dirty_perm", "bucket_rows",
            "merge_dirty", "append_overflow"}),
    ("MDB", {"accumulate", "stage", "scatter_rows", "filter_or",
             "merge_partition", "bucket_rows", "merge_dirty",
             "append_overflow"}),
])
def test_update_program_names_its_steps(scheme, scopes):
    cfg = _cfg(scheme)
    state = tj.init(cfg)
    toks = np.zeros(256, np.int32)
    parts = _op_path_parts(tj.update.lower(cfg, state, toks))
    assert scopes <= parts, sorted(scopes - parts)
    # the overflow append compacts by per-row counts; only MDB's
    # partitioned change segment stages through scatter_rows
    assert ("scatter_rows" in parts) == (scheme == "MDB")
    if scheme != "MB":
        flush = _op_path_parts(tj.flush.lower(cfg, state))
        merge = "drain_log" if scheme == "MDB-L" else "merge_partition"
        assert {merge, "bucket_rows", "merge_dirty",
                "append_overflow"} <= flush


@pytest.mark.parametrize("scheme", ["MDB-L", "MB"])
def test_read_programs_name_their_steps(scheme):
    cfg = _cfg(scheme)
    state = tj.init(cfg)
    q = np.zeros(128, np.int32)
    parts = _op_path_parts(tj.lookup_ex.lower(cfg, state, q))
    want = {"query_blocked", "filter_pass", "query_waves", "scan_overflow"}
    if scheme != "MB":
        want.add("scan_log")
    assert want <= parts, sorted(want - parts)
    assert "filter_probe" in _op_path_parts(tj.filter_probe.lower(cfg, state,
                                                                  q))


def test_rebuild_filters_names_its_step():
    import jax
    cfg = _cfg("MDB-L")
    from repro.core import segments as seg
    lowered = jax.jit(lambda st: seg.rebuild_filters(cfg.pair, st)).lower(
        tj.init(cfg))
    assert {"rebuild_filters", "filter_or"} <= _op_path_parts(lowered)


def test_touched_modules_lint_clean():
    from pathlib import Path

    from repro.analysis import flashlint
    core = Path(tj.__file__).resolve().parent
    files = [core / f for f in ("spans.py", "store.py", "write_engine.py",
                                "query_engine.py", "table_jax.py",
                                "segments.py")]
    files.append(core.parent / "kernels" / "flash_hash" / "ops.py")
    violations, n = flashlint.lint_paths(files)
    assert n == len(files)
    assert violations == [], "\n".join(v.format() for v in violations)
