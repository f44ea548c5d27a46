"""table.tile_stores_per_ktoken (tiles/ktoken): blocks rewritten by the
merges (``TableStats.tile_stores``, the paper's cleans) per thousand
tokens ingested."""


def read(run):
    if not run.tokens or "tile_stores" not in run.counters:
        return None
    return run.counters["tile_stores"] * 1e3 / run.tokens
