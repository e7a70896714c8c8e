"""The one traffic generator: queries and arrivals from a traffic file and
``--seed``.

A traffic file (``bench/traffic/<mix>.json``) holds:

* ``profile``: the serving profile every request carries (``mode``,
  ``strategy``, ``measure``, ``k``; ``df_cap`` for DRB/OR, pinned so the
  gather width is a shape of the configuration, not of the batch);
* ``words``: ``[lo, hi]``, words per query, uniform;
* ``bands``: the paper's document-frequency bands the words come from, in
  equal shares; all words of one query share a band;
* ``arrivals``: ``{"loop": "open", "rate_qps": r}`` (Poisson arrivals at a
  fixed rate) or ``{"loop": "closed", "clients": n}`` (n clients, each
  sending its next request when its last one returns);
* ``max_wait_ms``: the server's coalescing wait;
* ``check_sample``: how many answered requests the reference checks;
* ``limits``: the limit of each number ``check.py`` compares.

Every seed gets the same work.  Queries are drawn in blocks that hold each
(band, words per query) pair once; block ``j`` and the document-frequency
quantile of each of its words are drawn from the fixed stream
``SHAPE_SEED`` alone.  A quantile picks a word of the band by its rank in
(document frequency, Zipf rank) order, and the collection is the same for
every seed up to the names of its words (``collection.make``), so every
seed asks for the same words under other ids.  An open loop sends exactly
``round(rate * seconds)`` requests: the first that many queries of the
blocks, in their fixed order, at due times whose gaps are one fixed set of
exponential draws (a Poisson process) scaled to the window; so every seed
offers the same schedule, under other word ids.  (With the order drawn
from the seed, the DR/OR cell's 95th percentile at 4/5 of the knee moved
by 46-86% between seeds on one TPU v5e, and by 0-1% between two runs of
one seed.)  A closed loop takes block after block, each in the seed's
order: there a batch's time does not depend on which requests share it.
"""
from __future__ import annotations

import itertools
import threading

import numpy as np

SHAPE_SEED = 0
PAPER_DOCS = 345_778
PAPER_BANDS = {"i": (10, 100), "ii": (101, 1000), "iii": (1001, 10_000),
               "iv": (10_001, 100_000)}


def fdoc_bands(n_docs: int) -> dict[str, tuple[int, int]]:
    """The paper's four document-frequency bands rescaled to ``n_docs``
    (a copy of ``text/corpus.fdoc_bands``)."""
    scale = n_docs / PAPER_DOCS
    bands = {}
    for name, (lo, hi) in PAPER_BANDS.items():
        lo_s = max(2, int(lo * scale)) if scale < 1 else lo
        hi_s = max(lo_s + 1, int(hi * scale)) if scale < 1 else hi
        bands[name] = (lo_s, min(hi_s, n_docs))
    return bands


class Queries:
    """An endless, thread-safe stream of queries for one traffic mix over
    the collection ``coll`` (``collection.Collection``, indexed)."""

    def __init__(self, traffic: dict, coll, seed: int):
        bands = fdoc_bands(coll.n_docs)
        df = coll.df
        self.pools = []             # per band: word ids by (df, Zipf rank)
        for name in traffic["bands"]:
            lo, hi = bands[name]
            pool = np.flatnonzero((df >= lo) & (df <= hi))
            pool = pool[pool > 0]
            if len(pool) < traffic["words"][1]:
                raise ValueError(f"band {name} {bands[name]} holds "
                                 f"{len(pool)} words")
            self.pools.append(pool[np.lexsort((coll.rank[pool], df[pool]))])
        lo, hi = traffic["words"]
        self.pairs = list(itertools.product(range(len(self.pools)),
                                            range(lo, hi + 1)))
        self.rng = np.random.default_rng(seed)
        self.n_blocks = 0
        self._block: list = []
        self._lock = threading.Lock()

    def __iter__(self):
        return self

    def _specs(self, j: int) -> list[tuple]:
        """Block ``j``, in the fixed order: (band, quantiles) pairs."""
        shape = np.random.default_rng([SHAPE_SEED, j])
        hi = self.pairs[-1][1]
        return [(b, shape.random(hi)[:n]) for b, n in self.pairs]

    def __next__(self) -> list[int]:
        with self._lock:
            if not self._block:
                block = self._specs(self.n_blocks)
                self.n_blocks += 1
                self._block = [block[i]
                               for i in self.rng.permutation(len(block))]
            spec = self._block.pop()
        return self.words(spec)

    def window(self, n: int) -> list[list[int]]:
        """The first ``n`` queries of the blocks, in their fixed order."""
        specs, j = [], 0
        while len(specs) < n:
            specs += self._specs(j)
            j += 1
        return [self.words(spec) for spec in specs[:n]]

    def words(self, spec: tuple) -> list[int]:
        band, quantiles = spec
        pool = self.pools[band]
        picks = []
        for u in quantiles:             # distinct words, nearest free rank
            i = int(u * len(pool))
            while i in picks:
                i = (i + 1) % len(pool)
            picks.append(i)
        return [int(pool[i]) for i in picks]

    def warmup_set(self) -> list[list[int]]:
        """One query of every length the mix sends (every Q bucket), from
        the first band, without touching the measured stream."""
        lens = sorted({n for _, n in self.pairs})
        return [[int(w) for w in self.pools[0][:n]] for n in lens]


def open_offsets(rate_qps: float, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of an open loop:
    ``round(rate * seconds)`` arrivals whose gaps are one fixed set of
    exponential draws, scaled to fill the window."""
    n = int(round(rate_qps * seconds))
    gaps = np.random.default_rng(SHAPE_SEED).exponential(1.0, n + 1)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps)[:n]
