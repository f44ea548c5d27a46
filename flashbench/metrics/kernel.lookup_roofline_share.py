"""kernel.lookup_roofline_share (%): the least time the HBM traffic the
lookups need could take at peak bandwidth (a Bloom row per key probed,
keys and counts of each block fetched: ``QueryEngineStats``), over the
device time of the lookup and filter programs in the trace."""
import roofline

PROGRAMS = ("jit_lookup_ex", "jit_filter_probe")


def read(run):
    if run.trace is None or not run.keys:
        return None
    device_s = sum(s for p, s in run.trace["programs"].items()
                   if p in PROGRAMS)
    c = run.counters
    probed = c["query_device_queries"] + c["query_filter_negatives"]
    nbytes = roofline.lookup_bytes(probed, c["query_tile_loads"],
                                   run.block_entries, run.filter_words)
    return roofline.share(nbytes, device_s, run.peak["hbm_bytes_per_s"])
