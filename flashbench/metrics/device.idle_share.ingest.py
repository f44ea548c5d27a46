"""device.idle_share.ingest (%): the share of the counted ingest stretch
in which no operation ran on the device (trace: 1 - busy union / window,
averaged over the chips used)."""


def read(run):
    t = run.trace
    if t is None or not run.tokens or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
