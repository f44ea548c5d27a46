"""table.merges_per_mtoken (merges/Mtoken): device merges
(``TableStats.merges``) per million tokens ingested."""


def read(run):
    if not run.tokens or "merges" not in run.counters:
        return None
    return run.counters["merges"] * 1e6 / run.tokens
