"""kernel.write_roofline_share (%): the least time the HBM traffic the
merges and stages need could take at peak bandwidth (dirty blocks read
and written with their Bloom rows, staged entries appended; from the
``TableStats`` counters), over the device time of the whole update and
flush programs in the trace (every fusion and sort of them, not only the
kernel named inside)."""
import roofline

PROGRAMS = ("jit__update_impl", "jit_flush")


def read(run):
    if run.trace is None or not run.tokens:
        return None
    device_s = sum(s for p, s in run.trace["programs"].items()
                   if p in PROGRAMS)
    c = run.counters
    nbytes = roofline.write_bytes(c["tile_loads"], c["tile_stores"],
                                  c["staged_entries"], run.block_entries,
                                  run.filter_words)
    return roofline.share(nbytes, device_s, run.peak["hbm_bytes_per_s"])
