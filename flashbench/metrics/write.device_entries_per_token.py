"""write.device_entries_per_token (entries/token): entries the H_R fold
sent to the device per token ingested (``dispatched_entries`` / tokens);
what H_R's dedup leaves for the merges."""


def read(run):
    if not run.tokens or "write_dispatched_entries" not in run.counters:
        return None
    return run.counters["write_dispatched_entries"] / run.tokens
