"""Reduce a profiler trace to what the per-layer metrics read.

Two steps, so that the second can be checked on a small recorded trace:

* :func:`load_events` reads an ``.xplane.pb`` written by ``jax.profiler``
  into flat rows ``(plane, line, name, start_ns, dur_ns, module)``;
* :func:`reduce_events` takes the rows and the benchmark's window and
  returns device busy time, device time by program and by operation,
  collective time, and the device's idle time by what the benchmark's
  own host spans were doing.

The window is given by the benchmark's own host spans: it runs from the
start of the first span named ``window_span`` to the end of the
``window_count``-th one. Device operations are the events of a device
plane (``/device:...``) on its ``XLA Ops`` line; whole programs are the
events of its ``XLA Modules`` line, named by their jitted function
(``jit__update_impl``, ``jit_flush``, ``jit_lookup_ex``,
``jit_filter_probe``, ...); an op is charged to the program whose event
holds its start.
"""
from __future__ import annotations

import bisect
import re
from typing import Iterable, List, Sequence, Tuple

Row = Tuple[str, str, str, float, float, str]

HOST_SPANS = ("update", "flush", "query")   # the benchmark's call spans
BETWEEN = "between_calls"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_COLLECTIVE = re.compile(
    r"all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|all_to_all|psum", re.I)
_SUFFIX = re.compile(r"\(\d+\)$")
_HLO = re.compile(r"%?([\w.\-]+) = (\([^=]*?\)|\S+)")


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load_events(path) -> List[Row]:
    """Flat event rows of an ``.xplane.pb``: the device planes' op and
    program lines in full, and the benchmark's own spans from the host
    plane. An op row carries the program it ran in (the ``XLA Modules``
    event around its start) and a short name, ``<op> <shape>``."""
    from jax.profiler import ProfileData
    rows: List[Row] = []
    keep = set(HOST_SPANS) | {"group"}
    for plane in ProfileData.from_file(str(path)).planes:
        if is_device_plane(plane.name):
            rows += _device_rows(plane)
        elif plane.name.startswith("/host:CPU"):
            rows += [(plane.name, line.name, ev.name, float(ev.start_ns),
                      float(ev.duration_ns), "")
                     for line in plane.lines for ev in line.events
                     if ev.name in keep]
    return rows


def _device_rows(plane) -> List[Row]:
    mods, ops = [], []
    for line in plane.lines:
        if line.name in (OPS_LINE, MODULES_LINE):
            dest = mods if line.name == MODULES_LINE else ops
            dest += [(float(ev.start_ns), float(ev.duration_ns), ev.name)
                     for ev in line.events]
    mods.sort()
    starts = [m[0] for m in mods]
    rows = [(plane.name, MODULES_LINE, name, s, d, program_name(name))
            for s, d, name in mods]
    for s, d, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and s < mods[i][0] + mods[i][1]
        rows.append((plane.name, OPS_LINE, op_name(name), s, d,
                     program_name(mods[i][2]) if inside else ""))
    return rows


def op_name(hlo: str) -> str:
    """``%fusion.141 = s32[8388608]{0:T(1024)} fusion(...)`` →
    ``fusion.141 s32[8388608]`` (a tuple result shows as ``tuple``)."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:80]
    shape = m.group(2)
    shape = "tuple" if shape.startswith("(") else re.sub(r"\{.*", "", shape)
    return f"{m.group(1)} {shape}"


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w0: float, w1: float):
    a, b = max(a, w0), min(b, w1)
    return (a, b) if b > a else None


def program_name(name: str, module: str = "") -> str:
    """``jit__update_impl(12)`` → ``jit__update_impl``."""
    return _SUFFIX.sub("", module or name)


def reduce_events(rows: Sequence[Row], window_span: str,
                  window_count: int) -> dict:
    """Busy, idle, program and operation times inside the window (seconds;
    device numbers averaged over the device planes)."""
    host = sorted((r for r in rows if not is_device_plane(r[0])),
                  key=lambda r: r[3])
    marks = [r for r in host if r[2] == window_span][:window_count]
    if not marks:
        raise ValueError(f"no host span {window_span!r} in the trace")
    w0 = marks[0][3]
    w1 = marks[-1][3] + marks[-1][4]
    devices = sorted({r[0] for r in rows if is_device_plane(r[0])})
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy = 0.0
    programs: dict = {}
    ops: dict = {}
    collective = 0.0
    idle_by: dict = {}
    calls = [(r[3], r[3] + r[4], r[2]) for r in host if r[2] in HOST_SPANS]
    for di, dev in enumerate(devices):
        op_iv = []
        dev_ops = []
        for plane, line, name, start, dur, module in rows:
            if plane != dev:
                continue
            iv = _clip(start, start + dur, w0, w1)
            if iv is None:
                continue
            if line == MODULES_LINE:
                p = program_name(name, module)
                programs[p] = programs.get(p, 0.0) + iv[1] - iv[0]
            elif line == OPS_LINE:
                op_iv.append(iv)
                dev_ops.append((iv, name, module))
        # the op line nests: a while or conditional op encloses the ops of
        # its body. Operation time goes to the leaves, so nothing counts
        # twice; the busy union below takes every op.
        dev_ops.sort(key=lambda o: (o[0][0], -o[0][1]))
        for j, ((a, b), name, module) in enumerate(dev_ops):
            if j + 1 < len(dev_ops) and dev_ops[j + 1][0][0] < b:
                continue
            key = f"{module}/{name}" if module else name
            ops[key] = ops.get(key, 0.0) + b - a
            if _COLLECTIVE.search(name):
                collective += b - a
        if not op_iv:          # a plane with modules but no op line
            op_iv = [iv for plane, line, _, s, d, _ in rows
                     if plane == dev and line == MODULES_LINE
                     for iv in [_clip(s, s + d, w0, w1)] if iv]
        merged = _union(op_iv)
        busy += sum(b - a for a, b in merged)
        if di == 0:
            idle_by = _idle_by_host(merged, w0, w1, calls)
    n = len(devices)
    scale = 1e-9 / n
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * scale,
        "devices": n,
        "programs": {k: v * scale for k, v in programs.items()},
        "ops": {k: v * scale for k, v in ops.items()},
        "collective_s": collective * scale,
        "idle_by_host": {k: v * 1e-9 for k, v in idle_by.items()},
    }


def _idle_by_host(busy, w0, w1, calls) -> dict:
    """Idle stretches of one device, each charged to the benchmark call
    running on the host at its midpoint (the latest-started span that
    covers it; the calls run one after another on one thread), or to
    ``between_calls``."""
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    calls = sorted(calls)
    starts = [c[0] for c in calls]
    out: dict = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        i = bisect.bisect_right(starts, mid) - 1
        label = calls[i][2] if i >= 0 and calls[i][1] >= mid else BETWEEN
        out[label] = out.get(label, 0.0) + (b - a)
    return out


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and idle time by what the host was doing."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_host"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
