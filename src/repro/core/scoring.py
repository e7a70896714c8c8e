"""Relevance scoring: tf-idf (the paper's measure) and Okapi BM25 (extension).

The paper stores df_w per word ("insignificant extra space" by Heaps' law) and
computes ``tfidf(w, d) = tf_{w,d} * log(N / df_w)``, summing over query words.

WTBC-DR's prioritized traversal requires the score to be *monotone over
concatenation of documents* (score(d1 ++ d2) >= max(score(d1), score(d2))).
tf-idf with raw tf satisfies this; BM25 does not (document-length
normalization), which is exactly why the paper notes BM25 fits the DRB
strategy only.  ``assert_dr_compatible`` enforces that at the API level.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.wtbc import WTBCIndex


def _on_host(*xs) -> bool:
    """True when the idf inputs are concrete arrays, not values being traced.

    Concrete idf tables are computed on the host in float64 and rounded to
    float32: the TPU's float32 ``log`` is a fast approximation, off by ~1e-4
    absolute on the chip — enough to move tf-idf scores past the oracle
    tolerance.  Traced calls (a core's in-jit default table) keep the jnp
    formula."""
    return not any(isinstance(x, jax.core.Tracer) for x in xs)


@dataclasses.dataclass(frozen=True)
class TfIdf:
    """score(d) = sum_w tf_{w,d} * ln(N / df_w)"""
    name: str = "tfidf"
    dr_compatible: bool = True

    def idf(self, idx: WTBCIndex) -> jnp.ndarray:
        if _on_host(idx.df, idx.n_docs):
            df = np.maximum(np.asarray(idx.df, np.float64), 1.0)
            return jnp.asarray(np.log(float(idx.n_docs) / df)
                               .astype(np.float32))
        df = jnp.maximum(idx.df.astype(jnp.float32), 1.0)
        return jnp.log(idx.n_docs.astype(jnp.float32) / df)

    def score(self, tf: jnp.ndarray, idf_w: jnp.ndarray,
              doc_len: jnp.ndarray | None = None,
              avg_dl: jnp.ndarray | None = None) -> jnp.ndarray:
        return jnp.sum(tf.astype(jnp.float32) * idf_w, axis=-1)


@dataclasses.dataclass(frozen=True)
class BM25:
    """Okapi BM25 (k1, b) — usable with WTBC-DRB (candidate-then-rank) only."""
    k1: float = 1.2
    b: float = 0.75
    name: str = "bm25"
    dr_compatible: bool = False

    def idf(self, idx: WTBCIndex) -> jnp.ndarray:
        if _on_host(idx.df, idx.n_docs):
            df = np.asarray(idx.df, np.float64)
            n = float(idx.n_docs)
            return jnp.asarray(np.log(1.0 + (n - df + 0.5) / (df + 0.5))
                               .astype(np.float32))
        df = idx.df.astype(jnp.float32)
        n = idx.n_docs.astype(jnp.float32)
        return jnp.log(1.0 + (n - df + 0.5) / (df + 0.5))

    def score(self, tf: jnp.ndarray, idf_w: jnp.ndarray,
              doc_len: jnp.ndarray | None = None,
              avg_dl: jnp.ndarray | None = None) -> jnp.ndarray:
        tf = tf.astype(jnp.float32)
        norm = 1.0 - self.b + self.b * (doc_len.astype(jnp.float32) / avg_dl)
        part = tf * (self.k1 + 1.0) / (tf + self.k1 * norm[..., None])
        return jnp.sum(part * idf_w, axis=-1)


def assert_dr_compatible(measure) -> None:
    if not measure.dr_compatible:
        raise ValueError(
            f"{measure.name} is not monotone over document concatenation; "
            "WTBC-DR's prioritized traversal requires tf-idf (paper §5). "
            "Use WTBC-DRB for BM25.")
