"""device.idle_share.lookup (%): the share of the lookup window in which
no operation ran on the device (trace: 1 - busy union / window)."""


def read(run):
    t = run.trace
    if t is None or not run.keys or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
