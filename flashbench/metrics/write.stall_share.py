"""write.stall_share (%): the share of the counted ingest stretch in
which the writer was blocked on a drain or a flush barrier
(``WriteEngineStats.stall_us`` over the host window)."""


def read(run):
    if not run.tokens or "write_stall_us" not in run.counters:
        return None
    return 100.0 * run.counters["write_stall_us"] * 1e-6 / run.window_s
