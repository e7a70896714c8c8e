"""The collection a cell serves, made from its configuration and ``--seed``.

The law is that of ``text/corpus.make_corpus``: word ids 1..V-1 drawn from
Zipf(``zipf_alpha``), document lengths lognormal with sigma
``length_sigma``.  The configuration fixes the collection up to the names of
its words; ``--seed`` only renames them:

* the document lengths, the number of occurrences of each Zipf rank and
  the place of every occurrence are drawn once, from ``shape_seed`` of the
  configuration, with the lengths scaled to sum to exactly ``n_tokens``;
* ``--seed`` draws which word id holds which Zipf rank.

So every seed gives the same index up to that renaming: the (s,c)-DC code's
sizes, the level sizes, the DRB bitmaps, and the places of each rank's
words.  No shape changes with the seed, so nothing recompiles, and a search
for the words of given ranks does the same work whatever the seed
(``traffic.Queries`` draws words by rank).  The raw tokens are kept on
the host for the reference.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Collection:
    tokens: np.ndarray        # (n_tokens,) int32 word ids, documents back to back
    starts: np.ndarray        # (n_docs + 1,) int64 document boundaries in tokens
    vocab_size: int
    sorted_docs: np.ndarray | None = None   # tokens' documents, by word
    word_start: np.ndarray | None = None    # (vocab_size + 1,) into it
    df: np.ndarray | None = None      # (vocab_size,) document frequency
    rank: np.ndarray | None = None    # (vocab_size,) Zipf rank of a word id

    @property
    def n_docs(self) -> int:
        return len(self.starts) - 1

    @property
    def n_tokens(self) -> int:
        return len(self.tokens)

    @property
    def doc_len(self) -> np.ndarray:
        return np.diff(self.starts)

    def doc_tokens(self) -> list[np.ndarray]:
        return np.split(self.tokens, self.starts[1:-1])

    def index(self) -> None:
        """Sort the token positions by word (stable, so by position within a
        word) and count each word's documents; the reference and the traffic
        generator read both."""
        order = np.argsort(self.tokens, kind="stable")
        words = self.tokens[order]
        docs = np.searchsorted(self.starts, order, side="right") - 1
        self.sorted_docs = docs.astype(np.int32)
        self.word_start = np.searchsorted(
            words, np.arange(self.vocab_size + 1, dtype=words.dtype))
        new = np.ones(len(words), dtype=bool)
        new[1:] = (words[1:] != words[:-1]) | (docs[1:] != docs[:-1])
        self.df = np.bincount(words[new], minlength=self.vocab_size)

    def postings(self, word: int) -> np.ndarray:
        """The document of every occurrence of ``word``, ascending."""
        return self.sorted_docs[self.word_start[word]:
                                self.word_start[word + 1]]


def shape(config: dict) -> tuple[np.ndarray, np.ndarray]:
    """(document lengths, occurrences per Zipf rank 1..V-1): the part of
    the collection that the configuration fixes."""
    rng = np.random.default_rng(config["shape_seed"])
    n_docs, n_tokens = config["n_docs"], config["n_tokens"]
    sigma = config["length_sigma"]
    # make_corpus's mean_doc_len is the lognormal median; mean = median *
    # exp(sigma^2 / 2)
    median = n_tokens / n_docs / np.exp(sigma * sigma / 2)
    raw = np.maximum(2.0, rng.lognormal(np.log(median), sigma, n_docs))
    lens = np.maximum(2, np.floor(raw * (n_tokens / raw.sum()))).astype(np.int64)
    short = n_tokens - int(lens.sum())
    # hand the rounding remainder to the longest documents, one token each
    # (or take it from them), so the total is exact
    step = 1 if short > 0 else -1
    idx = np.argsort(-lens, kind="stable")[:abs(short)]
    lens[idx] += step
    if int(lens.sum()) != n_tokens or lens.min() < 2:
        raise ValueError("document lengths do not sum to n_tokens")
    V = config["vocab_size"]
    ranks = np.arange(1, V, dtype=np.float64)
    p = ranks ** (-config["zipf_alpha"])
    counts = rng.multinomial(n_tokens, p / p.sum())
    return lens, counts


def make(config: dict, seed: int) -> Collection:
    lens, counts = shape(config)
    V = config["vocab_size"]
    places = np.random.default_rng([config["shape_seed"], 1])
    ranks = places.permutation(np.repeat(np.arange(V - 1, dtype=np.int32),
                                         counts))   # Zipf rank - 1
    word_of_rank = (np.random.default_rng(seed).permutation(V - 1)
                    + 1).astype(np.int32)
    rank = np.full(V, V - 1, dtype=np.int64)      # id 0 is no word
    rank[word_of_rank] = np.arange(V - 1)
    starts = np.zeros(len(lens) + 1, dtype=np.int64)
    np.cumsum(lens, out=starts[1:])
    return Collection(tokens=word_of_rank[ranks], starts=starts,
                      vocab_size=V, rank=rank)
