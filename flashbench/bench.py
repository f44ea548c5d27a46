"""The benchmark's harness: one cell, one seed, one measured window.

A cell is ``<config>.<mix>`` in ``BENCHMARK.json``. Everything that
belongs to one configuration, mix or metric is a file of its own, found
by its name:

* ``configs/<config>.json`` — the deployment (scheme, geometry, backend,
  corpus shape, pre-load, guarantees);
* ``traffic/<mix>.json`` — the mix's parameters, read by the one general
  generator (:mod:`generator`) and driven by the loop its ``kind`` names;
* ``metrics/<metric>.py`` — one reader per per-layer metric, called with
  the run's counters and trace reduction (:func:`read_metric`).

A run: set-up (data from the seed, pre-load through the program's own
entry points, warm-up of the window's shapes, ``flush(wait=True)``), the
measured window, then the correctness check against the plain reference
(:mod:`reference`), after the window and outside ``setup_s``.

The window of an ``ingest`` mix counts whole commit groups only: a group
is ``calls`` updates closed by ``store.flush(wait=True)``; set-up ends
with the same flush, so H_R, the in-flight buffer and the MDB-L log are
empty when the window starts. The rate is the tokens of the groups whose
flush returned inside the window over the time from the window's start
to the last such return, so every counted group starts and ends at the
same point of the merge cycle. The group whose flush returns after the
window has closed is finished, fed to the reference and checked, but
neither counted nor timed.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import generator  # noqa: E402
import reference  # noqa: E402
import roofline  # noqa: E402
import trace_reduce  # noqa: E402

CACHE_DIR = CHECKOUT / ".jax_cache"
TRACE_DIR = CHECKOUT / ".flashbench_trace"


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------
def load_cell(name: str) -> SimpleNamespace:
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"flashbench: no workload {name!r} in "
                         f"BENCHMARK.json; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((CHECKOUT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in reported)]
    return SimpleNamespace(name=name, chips=int(w["chips"]), cfg=cfg,
                           traffic=traffic, end_to_end=e2e, per_layer=layer)


def read_metric(metric: str, run) -> "float | None":
    """``metrics/<metric>.py``'s ``read(run)``: a number, or None where the
    run holds nothing for it to read."""
    spec = importlib.util.spec_from_file_location(
        "flashbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        HERE / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def preload_store(cfg: dict, corpus: generator.Corpus, counts: np.ndarray):
    """Open the cell's store holding ranks ``[0, P)`` with ``counts``,
    through the program's own entry points only: ``table_jax.update`` of
    an MB table (each call merges straight into the data segment) takes
    ``preload_chunk`` keys a call with their counts as deltas, and the
    state is handed to the cell's ``FlashStore``, which ends flushed."""
    if cfg["backend"] != "device":
        raise ValueError(f"backend {cfg['backend']!r}: the harness drives "
                         "the device backend only")
    keys = corpus.keys(np.arange(counts.size))
    step = int(cfg["preload_chunk"])
    import jax
    import jax.numpy as jnp
    from repro.core import table_jax as tj
    mb = tj.FlashTableConfig(q_log2=cfg["q_log2"], r_log2=cfg["r_log2"],
                             scheme="MB")
    state = tj.init(mb)
    for lo in range(0, keys.size, step):
        part = slice(lo, min(lo + step, keys.size))
        k = np.full(step, tj.EMPTY, np.int32)
        c = np.zeros(step, np.int32)
        k[:part.stop - lo] = keys[part]
        c[:part.stop - lo] = counts[part]
        state = tj.update(mb, state, jnp.asarray(k), jnp.asarray(c))
    jax.block_until_ready(state)
    from repro.core import FlashStore
    store = FlashStore.open(
        backend="device", scheme=cfg["scheme"], q_log2=cfg["q_log2"],
        r_log2=cfg["r_log2"], chunk=cfg["chunk"],
        query_chunk=cfg["query_chunk"], state=state)
    store.flush(wait=True)
    return store


def numeric_stats(store) -> dict:
    return {k: int(v) for k, v in store.stats().items()
            if isinstance(v, (int, np.integer)) and not isinstance(v, bool)}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


def span(name: str):
    """A host span in the profiler's trace (next to free when no trace
    is being taken, so traced and untraced runs run the same code)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# the windows, one per traffic kind
# ---------------------------------------------------------------------------
def ingest_window(store, corpus, ref, traffic: dict, seconds: float,
                  on_start, on_end):
    """Closed loop, one writer, commit groups (see the module docstring)."""
    calls, per_call = int(traffic["calls"]), int(traffic["tokens_per_call"])
    size = calls * per_call

    def make(g: int):
        ranks = corpus.group(g, size)
        return ranks, corpus.keys(ranks)

    def feed(g: int, n_calls: int, close: bool) -> None:
        ranks, keys = pool.pop(g) if g in pool else make(g)
        with span("group"):
            for c in range(n_calls):
                with span("update"):
                    store.update(keys[c * per_call:(c + 1) * per_call])
            if close:
                with span("flush"):
                    store.flush(wait=True)
        ref.add(ranks[:n_calls * per_call])

    pool = {}
    feed(0, calls, close=True)               # warm-up group
    t_warm = time.perf_counter()
    pool = {g: make(g) for g in range(1, int(traffic["pool_groups"]) + 1)}
    before = last = numeric_stats(store)
    on_start()
    t0 = t_last = time.perf_counter()
    closed, merges = [], []                   # per counted group
    g = 1
    while True:
        feed(g, calls, close=True)
        t = time.perf_counter()
        if t - t0 > seconds:
            break                            # flushed after the window
        t_last = t
        closed.append(t - t0)
        now = numeric_stats(store)
        merges.append(now["merges"] - last["merges"])
        last = now
        g += 1
    on_end()
    counted = g - 1
    # read-back tail: the next group's first calls stay in H_R and the log
    feed(g + 1, int(traffic["tail_calls"]), close=False)
    n_new = int(round(size * corpus.distinct_share))
    tokens = counted * size
    window_s = t_last - t0
    return SimpleNamespace(
        t0=t0, t_warm=t_warm, window_s=window_s, tokens=tokens, keys=0,
        attempted=tokens,
        counters=delta(last, before), span=("group", counted),
        e2e={"ingest_tokens_per_s": tokens / window_s if counted else None},
        detail={"groups_closed_s": closed, "group_merges": merges},
        new_ranks=(corpus.resident,
                   min(corpus.vocab, corpus.resident + (g + 1) * n_new)))


def lookup_window(store, corpus, ref, traffic: dict, seconds: float,
                  on_start, on_end):
    """Closed loop, one client: one ``store.query`` of ``batch`` distinct
    keys at a time, every answer kept for the check."""
    size = int(traffic["batch"])
    share = float(traffic["present_share"])
    for b in range(1, int(traffic["warm_batches"]) + 1):
        store.query(corpus.keys(corpus.lookup_batch(-b, size, share)))
    t_warm = time.perf_counter()
    n_pool = int(traffic["pool_batches"])
    pool = [corpus.lookup_batch(b, size, share) for b in range(n_pool)]
    pool_keys = [corpus.keys(r) for r in pool]
    before = numeric_stats(store)
    lat, answers = [], []
    on_start()
    t0 = time.perf_counter()
    while True:
        keys = pool_keys[len(lat) % n_pool]
        s = time.perf_counter()
        with span("query"):
            got = store.query(keys)
        e = time.perf_counter()
        lat.append(e - s)
        answers.append(got)
        if e - t0 >= seconds:
            break
    on_end()
    window_s = e - t0
    n = len(lat)
    return SimpleNamespace(
        t0=t0, t_warm=t_warm, window_s=window_s, tokens=0, keys=n * size,
        attempted=n * size, counters=delta(numeric_stats(store), before),
        span=("query", n),
        e2e={"lookup_keys_per_s": n * size / window_s,
             "lookup_p95_ms": float(np.percentile(lat, 95)) * 1e3},
        detail={"batches": n, "latency_median_ms":
                float(np.median(lat)) * 1e3},
        answers=answers, asked=[pool[i % n_pool] for i in range(n)])


WINDOWS = {"ingest": ingest_window, "lookup": lookup_window}


# ---------------------------------------------------------------------------
# correctness: the numbers compared, each with its limit
# ---------------------------------------------------------------------------
def read_back_ranks(corpus, ref, win, traffic: dict) -> np.ndarray:
    """The ranks an ingest run reads back, drawn from the seed: tokens fed
    (so by frequency: the hot keys with the largest counts are in it),
    ranks first seen since the pre-load, resident ranks, and ranks never
    inserted (answered through the Bloom pre-pass)."""
    n = int(traffic["check_keys"])
    lo, hi = win.new_ranks
    return np.unique(np.concatenate([
        corpus.sample(ref.fed(), n, 0),
        corpus.sample_range(lo, hi, n // 2, 1),
        corpus.sample_range(0, corpus.resident, n // 2, 2),
        corpus.sample_range(corpus.vocab, generator.KEY_SPACE, n // 2, 3)]))


def produced(store, corpus, ref, win, traffic: dict):
    """What the timed path produced and the ranks it answers for: every
    answer of a lookup window, or the counts an ingest run reads back."""
    if traffic["kind"] == "lookup":
        return np.concatenate(win.answers), np.concatenate(win.asked)
    ranks = read_back_ranks(corpus, ref, win, traffic)
    return store.query(corpus.keys(ranks)), ranks


def check(store, got, ranks, ref) -> dict:
    """The numbers compared with their limits: answers that differ from
    the reference's exact counts, and counts the table dropped."""
    want = ref.counts(ranks)
    return {"mismatches": {"value": reference.mismatches(got, want),
                           "limit": 0},
            "dropped": {"value": int(store.wear()["dropped"]), "limit": 0}}


def is_correct(checks: dict, n_checked: int) -> bool:
    """Every number within its limit, and something was compared."""
    return n_checked > 0 and all(c["value"] <= c["limit"]
                                 for c in checks.values())


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def devices_for(chips: int, require_chip: bool = True):
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform == "cpu":
        raise SystemExit(f"flashbench: no accelerator (JAX sees "
                         f"{devs[0].platform}); there is no CPU fallback")
    if len(devs) < chips:
        raise SystemExit(f"flashbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs[:chips]


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             require_chip: bool = True, answers=produced) -> dict:
    """Set up, measure, check; returns the result line as a dict.

    ``answers(store, corpus, ref, win, traffic)`` gives what is compared
    with the reference and the ranks it answers for; the control
    (``control.py``) puts the reference in lower precision in the
    program's place there."""
    import jax
    devs = devices_for(cell.chips, require_chip)
    phases = {"jax_ready": time.perf_counter() - t_start}
    kind = devs[0].device_kind
    peak = roofline.peaks(kind) if require_chip else {}
    cfg, traffic = cell.cfg, cell.traffic
    corpus = generator.Corpus(cfg, seed)
    pre = corpus.preload()
    ref = reference.Reference(pre)
    phases["preload_data"] = time.perf_counter() - t_start
    store = preload_store(cfg, corpus, pre)
    phases["preloaded"] = time.perf_counter() - t_start

    def on_start():
        if trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # host spans and device ops; no Python call tracing, which
            # would record every call of the H_R fold and slow the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    def on_end():
        if trace:
            jax.profiler.stop_trace()

    win = WINDOWS[traffic["kind"]](store, corpus, ref, traffic, seconds,
                                   on_start, on_end)
    setup_s = win.t0 - t_start
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devs)
    red = None
    if trace:
        red = trace_reduce.reduce_events(
            trace_reduce.load_events(_xplane(TRACE_DIR)), *win.span)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    got, ranks = answers(store, corpus, ref, win, traffic)
    checks = check(store, got, ranks, ref)
    filter_words = int(store.state.filter_words.shape[-1])
    store.close()
    run = SimpleNamespace(
        cell=cell.name, cfg=cfg, traffic=traffic, window_s=win.window_s,
        tokens=win.tokens, keys=win.keys, counters=win.counters,
        trace=red, peak=peak, block_entries=1 << cfg["r_log2"],
        filter_words=filter_words)
    if trace:
        metrics = {m["name"]: {"value": v, "unit": m["unit"]}
                   for m in cell.per_layer
                   for v in [read_metric(m["name"], run)] if v is not None}
    else:
        e2e = dict(win.e2e, setup_s=setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end
                   if e2e.get(m["name"]) is not None}
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(memory_peak)}
    out = {"correct": is_correct(checks, ranks.size),
           "attempted": int(win.attempted),
           "failed": int(checks["mismatches"]["value"]),
           "metrics": metrics, "device": device}
    if red is not None:
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = trace_reduce.breakdown(red)
    phases.update(warmed=win.t_warm - t_start, window_start=setup_s)
    out["window"] = dict(win.detail, seconds=win.window_s,
                         setup_phases_s=phases)
    out["checked"] = int(ranks.size)
    out["checks"] = checks
    return out


def _xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, holding every program (however quick to compile)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
