"""The plain reference: NumPy counts of what a run fed the store.

A counting table under the configurations' guarantees answers every key
with exactly the number of times it was counted: its pre-load count plus
every occurrence in the tokens fed since. The reference keeps those in
rank space (the generator's vocabulary ranks) and compares answers the
way ``chip_smoke.check_answers`` does, with ``np.unique`` counts. It
imports nothing of the program.
"""
from __future__ import annotations

import numpy as np


class Reference:
    """Counts by rank: ``preload[rank]`` for resident ranks, plus the
    occurrences of each rank in every batch passed to :meth:`add`."""

    def __init__(self, preload: np.ndarray):
        self.preload = np.asarray(preload, np.int64)
        self._fed: list = []

    def add(self, ranks: np.ndarray) -> None:
        self._fed.append(np.asarray(ranks, np.int64))

    def fed(self) -> np.ndarray:
        return (np.concatenate(self._fed) if self._fed
                else np.zeros(0, np.int64))

    def counts(self, ranks: np.ndarray, dtype=np.int64) -> np.ndarray:
        """Exact counts of ``ranks``, held in ``dtype`` (wrapping as that
        type does) and returned as int64."""
        ranks = np.asarray(ranks, np.int64)
        p = self.preload.size
        want = np.where(ranks < p, self.preload[np.minimum(ranks, p - 1)], 0)
        uniq, cnt = np.unique(self.fed(), return_counts=True)
        if uniq.size:
            pos = np.clip(np.searchsorted(uniq, ranks), 0, uniq.size - 1)
            want = want + np.where(uniq[pos] == ranks, cnt[pos], 0)
        return want.astype(dtype).astype(np.int64)


def mismatches(got: np.ndarray, want: np.ndarray) -> int:
    """Answers that differ from the reference."""
    got = np.asarray(got, np.int64).reshape(-1)
    want = np.asarray(want, np.int64).reshape(-1)
    if got.shape != want.shape:
        return int(max(got.size, want.size))
    return int(np.count_nonzero(got != want))
