"""Query results returned by :meth:`repro.engine.SearchEngine.search`.

A thin, backend-agnostic wrapper over the kernels' ``DRResult`` leaves: doc
ids / scores are always batched ``(B, k)`` device arrays (a single query is a
batch of one), plus the work counters the benchmarks report and the resolved
routing metadata (which strategy ``"auto"`` actually picked, which measure
scored, …) so callers never have to reverse-engineer the dispatch.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class SearchResults:
    """Top-k answers for a batch of queries.

    docs:    (B, k) int32 global document ids, -1 padded past ``n_found``.
    scores:  (B, k) float32, descending, -inf padded.
    n_found: (B,)   int32 documents actually found per query.
    work:    (B,)   int32 backend work counter — DR: queue pops (summed over
             shards when sharded); DRB/AND: candidate iterations; DRB/OR: the
             df cap the gather ran with.
    k / mode / strategy / measure: the resolved query parameters (``strategy``
             is post-"auto" routing, never "auto" itself).
    match_pos / match_len: positional payloads, present for the "phrase" /
             "near" modes only (None otherwise).  ``match_pos`` is the
             (B, k) doc-relative token offset of the first phrase match /
             of the minimal proximity window; ``match_len`` its width in
             tokens; both -1 padded past ``n_found``.
    beam_width: the frontier width the executor ran with (1 on loop-free
             paths).
    pops:    (B,) int32 segments / candidates actually examined (None on the
             positional paths) — together with ``work`` (loop trips) this is
             the beam's emitted-doc-overhead metric.
    overflowed: (B,) bool — a search heap dropped a push at capacity; the
             affected query's ranking may be incomplete and should not be
             trusted silently.  See :meth:`diagnostics`.
    padded:  (B,) int32 — dead beam lanes processed (pad-waste): pops +
             padded = lanes the loop actually paid for.  The active-frontier
             buckets (core/ranked.py) keep this near zero.  DRB/OR: its
             dead (word, document) lanes, Q x df_cap less the live ones.
             None on paths without padding.
    certified: (B, k) bool — anytime certification (DESIGN.md §11): a True
             slot provably equals the exact oracle's slot; always a prefix
             per row, and all-True whenever the search ran to completion.
             None on the positional paths (which are always exhaustive).
    score_bound: (B,) float32 — per-row score upper bound on every document
             NOT in ``docs`` (-inf when the frontier was exhausted); the
             honest "how wrong can the uncertified tail be" dial.  None on
             the positional paths.
    sla:     the resolved SLA class this search ran under ("exact",
             "bounded", or "best_effort" — see engine/config.SLA_CLASSES).
    """
    docs: jnp.ndarray
    scores: jnp.ndarray
    n_found: jnp.ndarray
    work: jnp.ndarray
    k: int
    mode: str
    strategy: str
    measure: str
    match_pos: jnp.ndarray | None = None
    match_len: jnp.ndarray | None = None
    beam_width: int = 1
    pops: jnp.ndarray | None = None
    overflowed: jnp.ndarray | None = None
    padded: jnp.ndarray | None = None
    certified: jnp.ndarray | None = None
    score_bound: jnp.ndarray | None = None
    sla: str = "exact"

    def __post_init__(self):
        if self.docs.ndim != 2 or self.scores.shape != self.docs.shape:
            raise ValueError(f"expected batched (B, k) results, got docs "
                             f"{self.docs.shape} / scores {self.scores.shape}")
        for a in (self.match_pos, self.match_len):
            if a is not None and a.shape != self.docs.shape:
                raise ValueError(f"match payload shape {a.shape} != docs "
                                 f"shape {self.docs.shape}")

    def __len__(self) -> int:
        """Number of queries in the batch."""
        return int(self.docs.shape[0])

    def hits(self, b: int = 0) -> list[tuple[int, float]]:
        """Found ``(doc_id, score)`` pairs of query ``b``, best first."""
        n = int(self.n_found[b])
        docs = np.asarray(self.docs[b])[:n]
        scores = np.asarray(self.scores[b])[:n]
        return [(int(d), float(s)) for d, s in zip(docs, scores)]

    def matches(self, b: int = 0) -> list[tuple[int, float, int, int]]:
        """Found ``(doc_id, score, match_pos, match_len)`` tuples of query
        ``b``, best first — positional ("phrase" / "near") results only."""
        if self.match_pos is None or self.match_len is None:
            raise ValueError(f"mode={self.mode!r} results carry no match "
                             "positions; use .hits() (positions exist for "
                             "the 'phrase' and 'near' modes only)")
        n = int(self.n_found[b])
        return [(int(d), float(s), int(p), int(l)) for d, s, p, l in zip(
            np.asarray(self.docs[b])[:n], np.asarray(self.scores[b])[:n],
            np.asarray(self.match_pos[b])[:n], np.asarray(self.match_len[b])[:n])]

    def doc_ids(self) -> np.ndarray:
        """(B, k) numpy view of the document ids (-1 padded)."""
        return np.asarray(self.docs)

    def certified_fraction(self) -> float:
        """Certified slots / found slots over the whole batch (1.0 when the
        backend reports no certification data — exhaustive paths are exact)."""
        if self.certified is None:
            return 1.0
        found = int(np.sum(np.asarray(self.n_found)))
        if found == 0:
            return 1.0
        return float(np.sum(np.asarray(self.certified))) / found

    @property
    def diagnostics(self) -> dict:
        """Per-query health/work counters as host arrays.

        Keys: ``work`` (loop trips), ``beam_width``, and — when the backend
        reports them — ``pops`` (segments/candidates examined),
        ``overflowed`` (heap-capacity drops; a True entry means that query's
        ranking may be incomplete and the engine should be rebuilt with a
        larger ``heap_cap`` or queried with a smaller k) and ``padded``
        (dead beam lanes paid for — the pad-waste metric of the
        active-frontier buckets)."""
        out = {"work": np.asarray(self.work), "beam_width": self.beam_width,
               "sla": self.sla}
        if self.pops is not None:
            out["pops"] = np.asarray(self.pops)
        if self.overflowed is not None:
            out["overflowed"] = np.asarray(self.overflowed)
        if self.padded is not None:
            out["padded"] = np.asarray(self.padded)
        if self.certified is not None:
            out["certified"] = np.asarray(self.certified)
            out["certified_fraction"] = self.certified_fraction()
        if self.score_bound is not None:
            out["score_bound"] = np.asarray(self.score_bound)
        return out
