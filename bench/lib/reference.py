"""The plain reference: ranked top-k over the raw tokens, in NumPy.

It reads only the collection's raw tokens (``collection.Collection``), never
the engine, and follows the semantics of ``tests/oracle.py``:

* tf-idf: ``score = sum_w tf * ln(N / df_w)``; BM25 with k1 = 1.2, b = 0.75
  and ``idf = ln(1 + (N - df + 0.5) / (df + 0.5))``, over the collection's
  mean document length;
* DR, mode ``or``: a document is eligible when its score is above 0;
  ``and``: when every query word occurs in it;
* DRB: words whose tf-idf idf is below ``eps`` (and absent words) carry no
  bitmap and drop out of scoring and of ``or`` eligibility.

``oracle.py`` rescans every document for every query word; here each word's
occurrences come from one stable sort of the tokens (``Collection.index``),
which is the same count made once.  Scores are float64.  ``dtype`` computes
them in a lower precision instead (every operation rounded to it): that is
the control of ``check.py``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

K1, B = 1.2, 0.75


@dataclasses.dataclass
class Ranking:
    docs: np.ndarray       # candidate documents, ascending
    scores: np.ndarray     # float64 (or ``dtype``) score of each candidate
    eligible: np.ndarray   # bool, per candidate

    def top(self, k: int) -> tuple[np.ndarray, np.ndarray]:
        """The k best eligible documents, score descending, ties to the
        lower document (the engine's order)."""
        d, s = self.docs[self.eligible], self.scores[self.eligible]
        o = np.lexsort((d, -s.astype(np.float64)))[:k]
        return d[o], s[o]


def rank(coll, query, *, mode: str, strategy: str, measure: str,
         eps: float = 1e-6, dtype=np.float64) -> Ranking:
    """Every candidate document of ``query`` (a list of word ids) with its
    score and eligibility.  ``coll.index()`` must have run."""
    query = [int(w) for w in query]
    n = float(coll.n_docs)
    occ = [coll.postings(w) for w in query]
    docs = np.unique(np.concatenate(occ)) if occ else np.zeros(0, np.int32)
    tf = np.zeros((len(docs), len(query)), dtype=np.int64)
    for q, o in enumerate(occ):
        d, c = np.unique(o, return_counts=True)
        tf[np.searchsorted(docs, d), q] = c
    df = coll.df[query].astype(np.float64)
    if strategy == "drb":
        valid = (np.log(n / np.maximum(df, 1.0)) >= eps) & (df > 0)
    else:
        valid = np.ones(len(query), dtype=bool)
    if measure == "tfidf":
        idf = np.log(n / np.maximum(df, 1.0))
    elif measure == "bm25":
        idf = np.log(1.0 + (n - df + 0.5) / (df + 0.5))
    else:
        raise ValueError(f"unknown measure {measure!r}")
    idf = np.where(valid, idf, 0.0)
    scores = _score(tf, idf, coll.doc_len[docs], coll.doc_len.mean(),
                    measure, dtype)
    if mode == "and":
        if strategy == "drb":
            eligible = (bool(np.all(df > 0) and np.any(valid))
                        & np.all((tf > 0) | ~valid, axis=1))
        else:
            eligible = np.all(tf > 0, axis=1)
    elif mode == "or":
        if strategy == "drb":
            eligible = np.any((tf > 0) & valid, axis=1)
        else:
            eligible = scores.astype(np.float64) > 0.0
    else:
        raise ValueError(f"unsupported mode {mode!r}")
    return Ranking(docs=docs, scores=scores, eligible=np.asarray(eligible))


def _score(tf, idf, doc_len, avg_dl, measure, dtype):
    """(n_candidates,) scores, every operation rounded to ``dtype``."""
    t = dtype
    tf, idf = tf.astype(t), idf.astype(t)
    if measure == "tfidf":
        parts = tf * idf
    else:
        norm = (t(1.0 - B) + t(B) * (doc_len.astype(t) / t(avg_dl))).astype(t)
        parts = (tf * t(K1 + 1.0) / (tf + t(K1) * norm[:, None])) * idf
    out = np.zeros(len(tf), dtype=t)
    for q in range(parts.shape[1]):      # summed word by word, as rounded
        out = (out + parts[:, q]).astype(t)
    return out
