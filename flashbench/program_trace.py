"""The program's own steps in a profiler trace: host spans and device scopes.

``trace_reduce`` sees the program from outside: the benchmark's call
spans and device time by XLA program and instruction. The store also
names its steps (DESIGN.md §15): host spans ``flashstore.*``
(``repro.core.spans``) on the caller's thread and the drain worker's, and
a ``jax.named_scope`` around each step of its jitted programs, which the
trace keeps as each device operation's ``tf_op`` stat. This module reads
both, on the same rows and window as :func:`trace_reduce.reduce_events`,
and returns its result with these keys added:

* ``spans`` — self time of each program span inside the window: its
  time less what its child spans on the same thread cover;
* ``scopes`` — device time of the leaf operations by innermost named
  scope (``op_scopes``: the scope of each operation of ``ops``);
* ``idle_by_span`` — each idle gap of the device charged at its
  midpoint to the benchmark call (as ``idle_by_host``), then the
  innermost caller-side program span open there
  (``query/flashstore.query.lookup``), then ``+drain:<span>`` when the
  drain worker was inside a job; gaps with no program span keep the
  bare label.

:func:`shares` turns a result into the shares the write, table and
query layers are read by (PERF.md §3). Run as a script, it takes one
traced window of a cell through the benchmark's own set-up and window
loop and prints all of it as one JSON line::

    python3 flashbench/program_trace.py --workload meme-mb.lookup \\
        --seed 7 --seconds 51
"""
from __future__ import annotations

import bisect
import re
import sys
from pathlib import Path
from typing import List, Tuple

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import trace_reduce as tr  # noqa: E402

PROGRAM_SPAN = "flashstore."         # the program's host spans
WORKER_SPAN = "flashstore.drain."    # ... those of its drain worker
_PROGRAM_ID = re.compile(r"\((\d+)\)$")
# path components that JAX adds, not named scopes: jitted functions and
# transforms (``jit(f)``, ``vmap()``) and control flow
_STRUCTURAL = re.compile(
    r".*\(.*\)|while|body|cond|closed_call|core_call|remat|checkpoint"
    r"|pjit|scan|shard_map|branch_\d+_fun")

STAGING = ("bucket_rows", "scatter_rows", "append_overflow")
WRITE_HOST = ("flashstore.write.dedup", "flashstore.write.fold",
              "flashstore.write.seal")
QUERY_HOST = ("flashstore.query", "flashstore.query.lock",
              "flashstore.query.dedup", "flashstore.query.remember",
              "flashstore.query.overlay")
QUERY_SYNC = ("flashstore.query.filter", "flashstore.query.lookup")
UPDATE_PROGRAMS = ("jit__update_impl", "jit_flush")


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
def load_events(path) -> List[Tuple]:
    """:func:`trace_reduce.load_events`'s rows with a seventh field,
    ``where``: a host row's line index in its plane (two threads can
    share a line name), an operation's scope path (its ``tf_op``). Host
    rows also hold the program's ``flashstore.*`` spans."""
    from jax.profiler import ProfileData
    rows: List[Tuple] = []
    keep = set(tr.HOST_SPANS) | {"group"}
    paths = op_paths(path)
    for plane in ProfileData.from_file(str(path)).planes:
        if tr.is_device_plane(plane.name):
            rows += _device_rows(plane, paths.get(plane.name, {}))
        elif plane.name.startswith("/host:CPU"):
            rows += [(plane.name, line.name, ev.name, float(ev.start_ns),
                      float(ev.duration_ns), "", str(li))
                     for li, line in enumerate(plane.lines)
                     for ev in line.events
                     if ev.name in keep or ev.name.startswith(PROGRAM_SPAN)]
    return rows


def _device_rows(plane, paths: dict) -> List[Tuple]:
    mods, ops = [], []
    for line in plane.lines:
        if line.name == tr.MODULES_LINE:
            mods += [(float(e.start_ns), float(e.duration_ns), e.name)
                     for e in line.events]
        elif line.name == tr.OPS_LINE:
            ops += [(float(e.start_ns), float(e.duration_ns), e.name)
                    for e in line.events]
    mods.sort()
    starts = [m[0] for m in mods]
    rows = [(plane.name, tr.MODULES_LINE, name, s, d, tr.program_name(name),
             "") for s, d, name in mods]
    for s, d, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < mods[i][0] + mods[i][1]
        pid = _PROGRAM_ID.search(mods[i][2]) if inside else None
        where = paths.get((int(pid.group(1)) if pid else None, name),
                          paths.get((None, name), ""))
        rows.append((plane.name, tr.OPS_LINE, tr.op_name(name), s, d,
                     tr.program_name(mods[i][2]) if inside else "", where))
    return rows


def op_paths(path) -> dict:
    """``{device plane: {(program id, op text): op path}}``. The trace
    keeps each XLA operation's ``op_name`` (the ``jax.named_scope``
    path) as the ``tf_op`` stat of its event metadata, beside its
    ``program_id``; ``ProfileData`` does not expose event metadata, so
    it is read from the protobuf wire format here. An op text that has
    one path in every program is also keyed under ``None``."""
    with open(path, "rb") as f:
        space = f.read()
    out: dict = {}
    for field, plane in _fields(space):
        if field != 1:                              # XSpace.planes
            continue
        name, entries, stat_names = "", [], {}
        for f, v in _fields(plane):
            if f == 2:                              # XPlane.name
                name = v.decode()
            elif f == 4:                            # event_metadata entry
                entries.append(v)
            elif f == 5:                            # stat_metadata entry
                md = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        if tr.is_device_plane(name):
            out[name] = _plane_paths(entries, stat_names)
    return out


def _plane_paths(entries, stat_names: dict) -> dict:
    paths: dict = {}
    by_text: dict = {}
    for entry in entries:
        text, pid, op = "", None, ""
        for f, v in _fields(dict(_fields(entry)).get(2, b"")):
            if f == 2:                              # XEventMetadata.name
                text = v.decode(errors="replace")
            elif f == 5:                            # XStat
                stat = dict(_fields(v))
                kind = stat_names.get(stat.get(1))
                if kind == "program_id":
                    pid = stat.get(3, stat.get(4))
                elif kind == "tf_op":
                    op = (stat_names.get(stat[7], "") if 7 in stat
                          else stat.get(5, b"").decode(errors="replace"))
        if text and op:
            op = op[:-1] if op.endswith(":") else op
            paths[(pid, text)] = op
            by_text.setdefault(text, set()).add(op)
    for text, ops in by_text.items():
        if len(ops) == 1:
            paths[(None, text)] = next(iter(ops))
    return paths


def _fields(buf: bytes):
    """``(field number, value)`` of each field of a protobuf message:
    varints as ``int``, everything else as ``bytes``."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} is not read here")
        yield key >> 3, v


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def scope_of(path: str) -> str:
    """The innermost named scope of an op path, "" where it has none:
    ``jit(_update_impl)/stage/while/body/drain_log/bucket_rows/sort`` →
    ``bucket_rows``. The last component is the operation itself; a
    Pallas kernel's own name (under which ``pallas_call`` sits) counts
    as a scope."""
    for part in reversed(path.split("/")[:-1]):
        if part and not _STRUCTURAL.fullmatch(part):
            return part
    return ""


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------
def reduce_events(rows, window_span: str, window_count: int) -> dict:
    """:func:`trace_reduce.reduce_events` on the same rows and window,
    with ``spans``, ``scopes``, ``op_scopes`` and ``idle_by_span``."""
    red = tr.reduce_events([r[:6] for r in rows], window_span, window_count)
    host = sorted((r for r in rows if not tr.is_device_plane(r[0])),
                  key=lambda r: r[3])
    marks = [r for r in host if r[2] == window_span][:window_count]
    w0, w1 = marks[0][3], marks[-1][3] + marks[-1][4]
    devices = sorted({r[0] for r in rows if tr.is_device_plane(r[0])})
    scopes: dict = {}
    op_scopes: dict = {}
    gaps: list = []
    for di, dev in enumerate(devices):
        leaves, busy = _leaf_ops(rows, dev, w0, w1)
        for (a, b), key, scope in leaves:
            if scope:
                scopes[scope] = scopes.get(scope, 0.0) + b - a
                op_scopes[key] = scope
        if di == 0:
            gaps = _gaps(busy, w0, w1)
    threads = _threads(host)
    calls = [(r[3], r[3] + r[4], r[2]) for r in host
             if r[2] in tr.HOST_SPANS]
    scale = 1e-9 / len(devices)
    red.update(
        spans={k: v * 1e-9 for k, v in _self_times(threads, w0, w1).items()},
        scopes={k: v * scale for k, v in scopes.items()},
        op_scopes=op_scopes,
        idle_by_span={k: v * 1e-9 for k, v in
                      _idle_by_span(gaps, calls, threads).items()})
    return red


def _leaf_ops(rows, dev: str, w0: float, w1: float):
    """One device's leaf operations in the window, as
    ``trace_reduce`` charges them (an op that encloses the next one is
    not a leaf), with their scopes; and its busy union."""
    ops, spans = [], []
    for r in rows:
        if r[0] != dev or r[1] != tr.OPS_LINE:
            continue
        iv = tr._clip(r[3], r[3] + r[4], w0, w1)
        if iv is None:
            continue
        spans.append(iv)
        key = f"{r[5]}/{r[2]}" if r[5] else r[2]
        ops.append((iv, key, scope_of(r[6]) if len(r) > 6 else ""))
    ops.sort(key=lambda o: (o[0][0], -o[0][1]))
    leaves = [o for j, o in enumerate(ops)
              if not (j + 1 < len(ops) and ops[j + 1][0][0] < o[0][1])]
    return leaves, tr._union(spans)


def _gaps(busy, w0: float, w1: float) -> List[Tuple[float, float]]:
    """The stretches of ``[w0, w1]`` outside the busy union."""
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    return gaps


class _Nest:
    """The program spans of one role on one thread, nested as the thread
    opened them: the innermost one open at a time, each one's parent."""

    def __init__(self, spans):
        self.spans = sorted(spans, key=lambda s: (s[0], -s[1]))
        self.starts = [s[0] for s in self.spans]
        self.parent: List[int] = []
        open_: List[int] = []
        for i, (a, _, _) in enumerate(self.spans):
            while open_ and self.spans[open_[-1]][1] <= a:
                open_.pop()
            self.parent.append(open_[-1] if open_ else -1)
            open_.append(i)

    def at(self, t: float):
        """The innermost span open at ``t``, or None."""
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.spans[i][1] < t:
            i = self.parent[i]
        return self.spans[i] if i >= 0 else None


def _threads(host) -> dict:
    """``{"caller": [...], "worker": [...]}``: a :class:`_Nest` per
    thread and role. A thread is a host line, told apart from another of
    the same name by its index; the role comes from the span's name
    (``flashstore.drain.*`` is the worker's), since a store without a
    worker thread runs its drain jobs on the caller's."""
    by: dict = {}
    for r in host:
        if r[2].startswith(PROGRAM_SPAN):
            role = "worker" if r[2].startswith(WORKER_SPAN) else "caller"
            thread = (r[0], r[1], r[6] if len(r) > 6 else "")
            by.setdefault((role, thread), []).append(
                (r[3], r[3] + r[4], r[2]))
    out: dict = {"caller": [], "worker": []}
    for (role, _), spans in sorted(by.items()):
        out[role].append(_Nest(spans))
    return out


def _self_times(threads: dict, w0: float, w1: float) -> dict:
    out: dict = {}
    for nest in threads["caller"] + threads["worker"]:
        for (a, b, name), parent in zip(nest.spans, nest.parent):
            d = max(0.0, min(b, w1) - max(a, w0))
            out[name] = out.get(name, 0.0) + d
            if parent >= 0:
                up = nest.spans[parent][2]
                out[up] -= d
    return out


def _innermost(nests, t: float):
    found = [s for s in (n.at(t) for n in nests) if s is not None]
    return max(found)[2] if found else None


def _idle_by_span(gaps, calls, threads: dict) -> dict:
    calls = sorted(calls)
    starts = [c[0] for c in calls]
    out: dict = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        label = calls[i][2] if i >= 0 and calls[i][1] >= mid else tr.BETWEEN
        caller = _innermost(threads["caller"], mid)
        worker = _innermost(threads["worker"], mid)
        if caller:
            label += "/" + caller
        if worker:
            label += "+drain:" + worker
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def caller_span(label: str) -> str:
    """The caller-side program span of an ``idle_by_span`` label
    (``query/flashstore.query.lookup+drain:...`` →
    ``flashstore.query.lookup``), "" for a bare label."""
    return label.split("+drain:")[0].partition("/")[2]


# ---------------------------------------------------------------------------
# what the layers are read by (PERF.md §3), in %
# ---------------------------------------------------------------------------
def shares(red: dict) -> dict:
    """Each share, or None where the trace holds nothing it reads:

    * ``table.staging_share`` — device time under the ``bucket_rows``,
      ``scatter_rows`` and ``append_overflow`` scopes over that of the
      update and flush programs;
    * ``write.host_share`` — self time of ``flashstore.write.dedup``,
      ``.fold`` and ``.seal`` over the window;
    * ``query.idle_share.host`` — device idle under ``flashstore.query``
      itself or its ``.lock``, ``.dedup``, ``.remember`` and ``.overlay``
      steps, over the window;
    * ``query.idle_share.sync`` — device idle under
      ``flashstore.query.filter`` and ``.lookup`` (the round trips);
    * ``idle_share.bare`` — device idle charged to no program span."""
    w = red["window_s"]
    scopes, spans = red["scopes"], red["spans"]
    idle = red["idle_by_span"]
    out: dict = {}
    update_s = sum(v for p, v in red["programs"].items()
                   if p in UPDATE_PROGRAMS)
    out["table.staging_share"] = (
        100.0 * sum(scopes.get(s, 0.0) for s in STAGING) / update_s
        if update_s > 0 and any(s in scopes for s in STAGING) else None)
    out["write.host_share"] = (
        100.0 * sum(spans.get(s, 0.0) for s in WRITE_HOST) / w
        if any(s in spans for s in WRITE_HOST) else None)
    for name, group in (("query.idle_share.host", QUERY_HOST),
                        ("query.idle_share.sync", QUERY_SYNC)):
        out[name] = (100.0 * sum(v for k, v in idle.items()
                                 if caller_span(k) in group) / w
                     if any(s in spans for s in group) else None)
    out["idle_share.bare"] = 100.0 * sum(
        v for k, v in idle.items() if not caller_span(k)
        and "+drain:" not in k) / w
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    """:func:`trace_reduce.breakdown` with each operation's scope as a
    suffix (``jit__update_impl/fusion.141 s32[8388608] [scatter_rows]``)
    and the idle gaps by ``idle_by_span``."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    scope = red["op_scopes"]
    return {"device_ops": [[f"{k} [{scope[k]}]" if k in scope else k, v]
                           for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}


# ---------------------------------------------------------------------------
# one traced window of a cell
# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    import argparse
    import json
    import shutil
    import time
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="copy the trace's .xplane.pb to this path")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parent / "src"))
    import jax

    import bench
    import generator
    import reference
    cell = bench.load_cell(args.workload)
    bench.enable_compile_cache()
    devs = bench.devices_for(cell.chips)
    corpus = generator.Corpus(cell.cfg, args.seed)
    pre = corpus.preload()
    ref = reference.Reference(pre)
    store = bench.preload_store(cell.cfg, corpus, pre)
    out_dir = bench.TRACE_DIR.with_name(".program_trace")
    shutil.rmtree(out_dir, ignore_errors=True)

    def on_start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(out_dir), profiler_options=opts)

    win = bench.WINDOWS[cell.traffic["kind"]](
        store, corpus, ref, cell.traffic, args.seconds, on_start,
        jax.profiler.stop_trace)
    xplane = bench._xplane(out_dir)
    if args.keep:
        shutil.copy(xplane, args.keep)
    red = reduce_events(load_events(xplane), *win.span)
    shutil.rmtree(out_dir, ignore_errors=True)
    got, ranks = bench.produced(store, corpus, ref, win, cell.traffic)
    checks = bench.check(store, got, ranks, ref)
    store.close()
    print(json.dumps({
        "workload": cell.name, "seed": args.seed,
        "correct": bench.is_correct(checks, ranks.size),
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind},
        "setup_s": win.t0 - t_start, "window": win.detail,
        "e2e": win.e2e, "window_s": red["window_s"],
        "busy_s": red["busy_s"], "shares": shares(red),
        "programs": red["programs"], "scopes": red["scopes"],
        "spans": red["spans"], "idle_by_span": red["idle_by_span"],
        "idle_by_host": red["idle_by_host"],
        "breakdown": breakdown(red, 16)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
