"""Traffic from a seed: the corpus model and the batches each mix sends.

One general generator serves every traffic mix. A mix is a data file
(``traffic/<mix>.json``) of parameters; a deployment is a data file
(``configs/<config>.json``) of sizes. The program under test sees only the
arrays made here.

The corpus model follows the paper's corpora (§3.1): ``corpus_tokens``
tokens over a vocabulary of ``V = corpus_tokens * distinct_share`` keys,
Zipf (s=1) by rank. Rank ``i`` is the key ``key_of(i, seed)``, a bijection
of ``[0, 2**31)``, so keys cover all 31 bits and ranks ``>= V`` are keys the
corpus never holds.

* The pre-load is the vocabulary of the corpus's first ``preload_share``:
  ranks ``[0, P)`` with ``P = V * preload_share``, each with count
  ``1 + Poisson(expected Zipf draws of that rank in that share)``.
* The ingest stream continues the corpus in commit groups. Group ``g``
  introduces the next ``round(size * distinct_share)`` ranks once each
  (first occurrences spread evenly), and fills the rest with Zipf draws
  over the ranks introduced so far; the group is then shuffled. A group
  is a pure function of ``(seed, g)``.
* A lookup batch holds distinct keys: a share drawn uniformly from the
  resident vocabulary and the rest never inserted (ranks ``>= V``). A
  batch is a pure function of ``(seed, b)``.

``key_of`` and the Zipf draw are copied from ``chip_smoke.py``.
"""
from __future__ import annotations

import numpy as np

MASK31 = (1 << 31) - 1
KEY_SPACE = 1 << 31

# stream ids of the seeded generators, so that no two draws share a stream
_PRELOAD, _GROUP, _BATCH, _WARM, _SAMPLE = range(5)


def key_of(rank: np.ndarray, seed: int) -> np.ndarray:
    """Vocabulary rank → 31-bit key. Every step is a bijection of
    ``[0, 2**31)``, so distinct ranks give distinct keys, spread over the
    whole key space (never the reserved ``EMPTY = -1``)."""
    h = (np.asarray(rank).astype(np.uint64)
         + np.uint64((seed * 0x9E3779B1) & MASK31)) & np.uint64(MASK31)
    for shift, mult in ((15, 0x2C1B3C6D), (12, 0x297A2D39), (15, 1)):
        h ^= h >> np.uint64(shift)
        h = (h * np.uint64(mult)) & np.uint64(MASK31)
    return h.astype(np.int64)


def rng_for(seed: int, stream: int, index: int = 0) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, int(index)])


class Corpus:
    """The corpus of one deployment under one seed (see module docstring)."""

    def __init__(self, cfg: dict, seed: int):
        self.seed = int(seed)
        self.tokens = int(cfg["corpus_tokens"])
        self.distinct_share = float(cfg["distinct_share"])
        self.vocab = int(round(self.tokens * self.distinct_share))
        self.preload_share = float(cfg["preload_share"])
        self.resident = int(round(self.vocab * self.preload_share))
        # H_n for n = 1..V: the Zipf (s=1) normaliser of the first n ranks
        self._cdf = np.cumsum(1.0 / np.arange(1, self.vocab + 1))

    def keys(self, ranks: np.ndarray) -> np.ndarray:
        return key_of(ranks, self.seed)

    def preload(self) -> np.ndarray:
        """Counts of ranks ``[0, P)`` resident before the window."""
        p = self.resident
        draws = self.tokens * self.preload_share - p
        lam = draws / np.arange(1, p + 1) / self._cdf[p - 1]
        return 1 + rng_for(self.seed, _PRELOAD).poisson(lam)

    def group(self, g: int, size: int) -> np.ndarray:
        """Ranks of commit group ``g`` (``size`` tokens)."""
        n_new = int(round(size * self.distinct_share))
        lo = min(self.resident + g * n_new, self.vocab)
        hi = min(lo + n_new, self.vocab)
        rng = rng_for(self.seed, _GROUP, g)
        u = rng.random(size - (hi - lo)) * self._cdf[hi - 1]
        draws = np.minimum(np.searchsorted(self._cdf, u), hi - 1)
        return rng.permutation(np.concatenate([np.arange(lo, hi), draws]))

    def lookup_batch(self, b: int, size: int, present_share: float
                     ) -> np.ndarray:
        """Ranks of lookup batch ``b``: ``size`` distinct ranks, a share
        drawn uniformly from the resident vocabulary and the rest never
        inserted, shuffled. Warm-up batches take negative ``b``."""
        stream = _WARM if b < 0 else _BATCH
        rng = rng_for(self.seed, stream, abs(b))
        n_present = int(round(size * present_share))
        present = _distinct(lambda n: rng.integers(0, self.resident, n),
                            n_present)
        absent = _distinct(
            lambda n: rng.integers(self.vocab, KEY_SPACE, n),
            size - n_present)
        return rng.permutation(np.concatenate([present, absent]))

    def sample(self, ranks: np.ndarray, n: int, index: int) -> np.ndarray:
        """``n`` entries of ``ranks`` drawn uniformly (with repeats) from
        the seed: part of the correctness check's read-back sample."""
        if ranks.size == 0:
            return ranks
        return ranks[rng_for(self.seed, _SAMPLE, index)
                     .integers(0, ranks.size, n)]

    def sample_range(self, lo: int, hi: int, n: int, index: int
                     ) -> np.ndarray:
        """``n`` ranks drawn uniformly from ``[lo, hi)`` from the seed."""
        if hi <= lo:
            return np.zeros(0, np.int64)
        return rng_for(self.seed, _SAMPLE, index).integers(lo, hi, n)


def _distinct(draw, n: int) -> np.ndarray:
    """``n`` distinct values from repeated calls of ``draw(k)``; the
    values keep the order in which they were first drawn, so the result
    is a pure function of the generator's state."""
    out = np.zeros(0, np.int64)
    while out.size < n:
        cand = np.concatenate([out, draw(2 * (n - out.size) + 16)])
        _, first = np.unique(cand, return_index=True)
        out = cand[np.sort(first)][:n]
    return out
