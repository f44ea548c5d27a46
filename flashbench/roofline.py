"""Bytes the table's algorithms need, and the peaks they are held to.

A roofline share is the least time the chip could take for the work,
bytes over peak HBM bandwidth, divided by the device time the programs
took. The bytes count only the work the algorithm needs, from the
counters the engines keep, never the blocks a kernel grid happens to
walk: a merge reads and writes each dirty block once (its keys, counts
and Bloom row), and an MDB-L stage appends each staged entry (key and
count) to the log once; a lookup reads the Bloom row of each key it
probes and the keys and counts of each block it fetches.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"
WORD = 4                          # bytes of a key, a count, a Bloom word


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``; a kind that is not in
    the table is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def tile_bytes(block_entries: int) -> int:
    """Keys and counts of one block."""
    return 2 * WORD * block_entries


def bloom_row_bytes(filter_words: int) -> int:
    return WORD * filter_words


def write_bytes(tile_loads: int, tile_stores: int, staged_entries: int,
                block_entries: int, filter_words: int) -> int:
    """HBM bytes the merges and stages of a stretch need."""
    per_tile = tile_bytes(block_entries) + bloom_row_bytes(filter_words)
    return (tile_loads + tile_stores) * per_tile + staged_entries * 2 * WORD


def lookup_bytes(probed_keys: int, tile_loads: int, block_entries: int,
                 filter_words: int) -> int:
    """HBM bytes the lookups of a stretch need: one Bloom row per key
    probed, one block per tile fetched."""
    return (probed_keys * bloom_row_bytes(filter_words)
            + tile_loads * tile_bytes(block_entries))


def share(nbytes: float, device_s: float, hbm_bytes_per_s: float):
    """Roofline share in %, or None where the trace timed no work."""
    if device_s <= 0 or nbytes <= 0:
        return None
    return 100.0 * (nbytes / hbm_bytes_per_s) / device_s
