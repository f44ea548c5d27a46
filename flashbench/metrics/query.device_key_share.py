"""query.device_key_share (%): the share of the keys asked that went to
the device lookup program: not answered by the hot cache, not ruled out
by the Bloom pre-pass (``device_queries`` / ``keys``)."""


def read(run):
    if not run.keys or "query_device_queries" not in run.counters:
        return None
    return 100.0 * run.counters["query_device_queries"] / run.keys
