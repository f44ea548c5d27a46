"""Byte counts of the roofline shares, against counts worked by hand on
a small table (``q_log2=10``, ``r_log2=7``: 8 blocks of 128 slots, Bloom
rows of 16 words), and the peaks table."""
import numpy as np
import pytest

import roofline
from repro.core import FlashStore
from repro.core.hashing import Pow2Hash, filter_words_for

Q_LOG2, R_LOG2 = 10, 7


def _keys_in_distinct_blocks(n):
    pair = Pow2Hash(q_log2=Q_LOG2, r_log2=R_LOG2)
    cand = np.arange(1, 4096, dtype=np.int64)
    blocks = np.asarray(pair.s(cand))
    _, first = np.unique(blocks, return_index=True)
    return cand[np.sort(first)][:n]


def test_widths():
    assert filter_words_for(1 << R_LOG2) == 16
    assert roofline.tile_bytes(128) == 1024        # 128 keys + 128 counts
    assert roofline.bloom_row_bytes(16) == 64


def test_write_and_lookup_bytes_by_hand():
    keys = _keys_in_distinct_blocks(4)
    with FlashStore.open(backend="device", scheme="MDB-L", q_log2=Q_LOG2,
                         r_log2=R_LOG2, chunk=16, query_chunk=16) as s:
        s.update(keys[:3])
        s.flush(wait=True)
        st = s.stats()
        # 3 keys staged in the log, then one merge of their 3 blocks
        assert (st["tile_loads"], st["tile_stores"],
                st["staged_entries"]) == (3, 3, 3)
        # 6 block passes x (1,024 B of tile + 64 B of Bloom row) + 3 x 8 B
        assert roofline.write_bytes(3, 3, 3, 128, 16) == 6552
        assert s.query(keys).tolist() == [1, 1, 1, 0]
        st = s.stats()
        probed = st["query_device_queries"] + st["query_filter_negatives"]
        # 4 keys probe a Bloom row each; the 3 present fetch their blocks
        assert (probed, st["query_tile_loads"]) == (4, 3)
        assert roofline.lookup_bytes(probed, 3, 128, 16) == 3328


def test_share():
    peak = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert peak == 819e9
    # 819 MB in 2 s at 819 GB/s: 0.05% of the roofline
    assert roofline.share(819e6, 2.0, peak) == pytest.approx(0.05)
    assert roofline.share(0, 1.0, peak) is None
    assert roofline.share(1e6, 0.0, peak) is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
